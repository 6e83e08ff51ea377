"""The one point generator: a mixture of Gaussian hotspots over a uniform
background, every parameter from the traffic mix's data file.

    {"hotspot_share": 0.9, "hotspots": 64, "zipf_s": 1.1,
     "sigma_m": [300, 3000], "lat0_deg": 40.7, "layout_seed": 20260927}

Where the hotspots lie and how wide each is (the layout) comes from
``layout_seed`` — places do not move from run to run, so every ``--seed``
does statistically the same work; which hotspot a point belongs to and
where it falls comes from ``--seed``. ``hotspot_share: 0`` is the uniform
mix. Points that land outside the box stay: they are misses.

Generated on the device in one jitted call (`make_generator`), so the host
feeds nothing; `jax.numpy` runs the same code on the CPU for the tests.
"""

from __future__ import annotations

import math

import numpy as np

_M_PER_DEG = 111_320.0


def layout(params: dict, bbox) -> dict:
    """Hotspot centres (H, 2), per-axis sigma in degrees (H, 2) and the
    cumulative Zipf weights (H,), all from ``layout_seed``."""
    h = int(params.get("hotspots", 0))
    rng = np.random.default_rng(int(params.get("layout_seed", 0)))
    xmin, ymin, xmax, ymax = bbox
    centres = np.column_stack(
        [rng.uniform(xmin, xmax, max(h, 1)), rng.uniform(ymin, ymax, max(h, 1))]
    )
    lo, hi = params.get("sigma_m", [1.0, 1.0])
    sigma_m = np.exp(rng.uniform(math.log(lo), math.log(hi), max(h, 1)))
    coslat = math.cos(math.radians(float(params.get("lat0_deg", 0.0))))
    sigma = np.column_stack(
        [sigma_m / (_M_PER_DEG * coslat), sigma_m / _M_PER_DEG]
    )
    w = np.arange(1, max(h, 1) + 1, dtype=np.float64) ** -float(
        params.get("zipf_s", 1.0)
    )
    return {
        "centres": centres, "sigma": sigma,
        "cum_weights": np.cumsum(w / w.sum()),
        "share": float(params.get("hotspot_share", 0.0)) if h else 0.0,
    }


def make_generator(params: dict, bbox, n: int, *, slots: int | None = None,
                   out_sharding=None):
    """Jitted ``gen(key) -> (n, 2)`` f64 points — or, with ``slots``,
    ``(slots, n, 2)`` made in the same one call."""
    import jax
    import jax.numpy as jnp

    lay = layout(params, bbox)
    centres = jnp.asarray(lay["centres"], jnp.float64)
    sigma = jnp.asarray(lay["sigma"], jnp.float64)
    cum = jnp.asarray(lay["cum_weights"], jnp.float32)
    share = lay["share"]
    lo = jnp.asarray(bbox[:2], jnp.float64)
    span = jnp.asarray([bbox[2] - bbox[0], bbox[3] - bbox[1]], jnp.float64)

    def one(key):
        k1, k2, k3, k4 = jax.random.split(key, 4)
        u = jax.random.uniform(k4, (n, 2), dtype=jnp.float32)
        uniform = lo + u * span
        if share <= 0.0:
            return uniform
        hot = jax.random.uniform(k1, (n,), dtype=jnp.float32) < share
        which = jnp.searchsorted(
            cum, jax.random.uniform(k2, (n,), dtype=jnp.float32)
        ).clip(0, cum.shape[0] - 1)
        z = jax.random.normal(k3, (n, 2), dtype=jnp.float32)
        spot = centres[which] + z * sigma[which]
        return jnp.where(hot[:, None], spot, uniform)

    if slots is None:
        fn = one
    else:
        def fn(key):
            return jnp.stack(
                [one(jax.random.fold_in(key, i)) for i in range(slots)]
            )
    return jax.jit(fn, out_shardings=out_sharding)


def seed_key(seed: int):
    """A PRNG key from any whole number (the driver's seeds pass 2**31)."""
    import jax

    return jax.random.PRNGKey(int(seed) % (2**63))
