"""A day's AIS as the ship-to-ship notebook groups it: one LINESTRING a
vessel a 15-minute window, every parameter from the configuration's
``fleet`` block.

    {"vessels": 4096, "window_minutes": 15, "ping_slots": 15,
     "pings": [5, 15], "one_metre_deg": 9e-06, "box": [...],
     "moored": {"share": 0.40, "places": 24, "zipf_s": 1.1,
                "sigma_km": [0.4, 2.5], "jitter_m": [10, 30],
                "speed_kn": [0.0, 0.5]},
     "lanes": {"share": 0.45, "count": 6, "speed_kn": [8, 16],
               "lateral_sigma_m": 300, "min_length_deg": 3.0},
     "free": {"speed_kn": [5, 14]},
     "transfers": {"pairs": 32, "windows": [4, 8], "gap_m": [30, 90],
                   "closing_kn": 2.0, "drift_kn": [0.2, 0.8],
                   "offset_km": [3, 12]},
     "layout_seed": 20261003}

Where the terminals, anchorages and lanes lie (the layout) comes from
``layout_seed``: places do not move from run to run, so every ``--seed``
does statistically the same work. Which vessel is where, how fast, how
often it reports and where the planted transfers happen comes from the
seed handed to :func:`table`.

The plane is the source's: longitude and latitude in degrees read as
planar coordinates, a metre ``one_metre_deg`` degrees on both axes (the
notebook's ``one_metre``). A vessel is one of

- **moored or at anchor**: a place drawn by Zipf weight, a spot Gaussian
  about it, a drift of under half a knot and a jitter of 10-30 m a ping;
- **under way in a lane**: one of ``count`` straight two-way lanes
  between two places, a constant speed, a lateral offset it keeps;
- **free**: anywhere in the box, any heading, a constant speed;
- **a planted transfer pair**: a mother ship drifting in a lightering
  area some kilometres off a place, and a daughter that closes on her at
  ``closing_kn``, lies ``gap_m`` off her side for ``windows`` whole
  windows and leaves again — the event the deployment exists to find.

A table is ``windows`` consecutive windows of the same fleet: row
``w * vessels + v`` is vessel ``v`` in window ``w``, its pings the
``pings[0]..pings[1]`` of the window's ``ping_slots`` one-minute slots
the vessel reported in. All array code: one call makes a four-hour
table of 65,536 tracks in well under a second.
"""

from __future__ import annotations

import numpy as np

KNOT_M_S = 1852.0 / 3600.0


def layout(fleet: dict) -> dict:
    """Places (P, 2), their spreads in degrees (P,), their cumulative Zipf
    weights (P,) and the lanes' two ends (L, 2, 2), from ``layout_seed``."""
    rng = np.random.default_rng(int(fleet["layout_seed"]))
    x0, y0, x1, y1 = fleet["box"]
    one_m = float(fleet["one_metre_deg"])
    moored, lanes = fleet["moored"], fleet["lanes"]
    n = int(moored["places"])
    # places keep a margin of the box: a track never starts at its edge
    mx, my = 0.04 * (x1 - x0), 0.08 * (y1 - y0)
    places = np.column_stack(
        [rng.uniform(x0 + mx, x1 - mx, n), rng.uniform(y0 + my, y1 - my, n)]
    )
    lo, hi = moored["sigma_km"]
    sigma = np.exp(rng.uniform(np.log(lo), np.log(hi), n)) * 1000.0 * one_m
    w = np.arange(1, n + 1, dtype=np.float64) ** -float(moored["zipf_s"])
    ends = np.zeros((int(lanes["count"]), 2, 2))
    for k in range(ends.shape[0]):
        # a lane joins two places far enough apart that no vessel reaches
        # its end inside a table
        for _ in range(1000):
            a, b = rng.choice(n, 2, replace=False)
            if np.hypot(*(places[a] - places[b])) >= float(lanes["min_length_deg"]):
                break
        ends[k] = places[a], places[b]
    return {"places": places, "sigma": sigma,
            "cum_weights": np.cumsum(w / w.sum()), "lanes": ends}


def _uniform(rng, bounds, n):
    return rng.uniform(float(bounds[0]), float(bounds[1]), n)


def table(fleet: dict, windows: int, seed, lay: dict | None = None) -> dict:
    """One table of ``windows`` consecutive windows from ``seed`` (a whole
    number, or a few: a run's seed and the table's place in its pool):
    ``{"xy" (V, 2) f64, "offsets" (T + 1,) i64, "window" (T,) i64,
    "vessel" (T,) i64, "pings" (T,) i64, "radius" (T,) f64, "planted"
    (K, 3) i64 rows (window, vessel a, vessel b) with a < b: the windows
    a planted pair lies side by side from the first ping to the last}``."""
    lay = layout(fleet) if lay is None else lay
    rng = np.random.default_rng([*np.atleast_1d(seed).tolist(), 0x5A15])
    n = int(fleet["vessels"])
    one_m = float(fleet["one_metre_deg"])
    kn = KNOT_M_S * one_m  # degrees a second a knot
    slots = int(fleet["ping_slots"])
    win_s = 60.0 * float(fleet["window_minutes"])
    span_s = win_s * windows
    x0, y0, x1, y1 = fleet["box"]
    moored, lanes, free, tr = (
        fleet["moored"], fleet["lanes"], fleet["free"], fleet["transfers"]
    )

    # ---- the fleet at t = 0: a start point and a velocity a vessel
    kind = rng.permutation(n)
    n_moor = int(round(moored["share"] * n))
    n_lane = int(round(lanes["share"] * n))
    is_moor = np.zeros(n, bool)
    is_lane = np.zeros(n, bool)
    is_moor[kind[:n_moor]] = True
    is_lane[kind[n_moor : n_moor + n_lane]] = True
    p0 = np.column_stack([rng.uniform(x0, x1, n), rng.uniform(y0, y1, n)])
    hdg = rng.uniform(0.0, 2.0 * np.pi, n)
    speed = _uniform(rng, free["speed_kn"], n) * kn
    jitter = np.zeros(n)

    place = np.searchsorted(lay["cum_weights"], rng.uniform(size=n))
    place = np.minimum(place, lay["places"].shape[0] - 1)
    at = lay["places"][place] + rng.normal(size=(n, 2)) * lay["sigma"][place, None]
    p0[is_moor] = at[is_moor]
    speed[is_moor] = _uniform(rng, moored["speed_kn"], n)[is_moor] * kn
    jitter[is_moor] = _uniform(rng, moored["jitter_m"], n)[is_moor] * one_m

    lane = rng.integers(0, lay["lanes"].shape[0], n)
    a, b = lay["lanes"][lane, 0], lay["lanes"][lane, 1]
    back = rng.uniform(size=n) < 0.5
    a, b = np.where(back[:, None], b, a), np.where(back[:, None], a, b)
    length = np.hypot(*(b - a).T)
    along = (b - a) / length[:, None]
    across = np.column_stack([-along[:, 1], along[:, 0]])
    v_lane = _uniform(rng, lanes["speed_kn"], n) * kn
    s0 = rng.uniform(size=n) * np.maximum(length - v_lane * span_s, 0.0)
    lateral = rng.normal(size=n) * float(lanes["lateral_sigma_m"]) * one_m
    start = a + along * s0[:, None] + across * lateral[:, None]
    p0[is_lane] = start[is_lane]
    speed[is_lane] = v_lane[is_lane]
    hdg[is_lane] = np.arctan2(along[:, 1], along[:, 0])[is_lane]
    vel = np.column_stack([np.cos(hdg), np.sin(hdg)]) * speed[:, None]

    # ---- planted transfers: 2 * pairs vessels taken from the free ones
    k = int(tr["pairs"])
    loose = np.flatnonzero(~is_moor & ~is_lane)
    chosen = np.sort(rng.choice(loose, 2 * k, replace=False))
    mother, daughter = chosen[0::2], chosen[1::2]  # mother < daughter
    spot = np.minimum(
        np.searchsorted(lay["cum_weights"], rng.uniform(size=k)),
        lay["places"].shape[0] - 1,
    )
    off = _uniform(rng, tr["offset_km"], k) * 1000.0 * one_m
    ang = rng.uniform(0.0, 2.0 * np.pi, k)
    p0[mother] = lay["places"][spot] + np.column_stack(
        [np.cos(ang), np.sin(ang)]
    ) * off[:, None]
    drift = rng.uniform(0.0, 2.0 * np.pi, k)
    vel[mother] = np.column_stack([np.cos(drift), np.sin(drift)]) * (
        _uniform(rng, tr["drift_kn"], k) * kn
    )[:, None]
    jitter[mother] = jitter[daughter] = 10.0 * one_m
    lo_w, hi_w = tr["windows"]
    dur_w = rng.integers(int(lo_w), int(hi_w) + 1, k)
    first_w = rng.integers(0, np.maximum(windows - dur_w, 0) + 1)
    # side by side from the first whole window's start to the last one's end
    t_on, t_off = first_w * win_s, (first_w + dur_w) * win_s
    side = rng.uniform(0.0, 2.0 * np.pi, k)
    side = np.column_stack([np.cos(side), np.sin(side)])
    gap = _uniform(rng, tr["gap_m"], k) * one_m
    closing = float(tr["closing_kn"]) * kn

    # ---- pings: which one-minute slots of each window a vessel reported in
    n_pings = rng.integers(int(fleet["pings"][0]), int(fleet["pings"][1]) + 1,
                           (windows, n))
    order = np.argsort(rng.uniform(size=(windows, n, slots)), axis=2)
    kept = np.sort(
        np.where(np.arange(slots) < n_pings[..., None], order, slots), axis=2
    )  # the kept slots ascending, then `slots` as filler
    live = kept < slots
    t = (
        np.arange(windows)[:, None, None] * win_s
        + kept * (win_s / slots) + rng.uniform(0.0, 20.0, kept.shape)
    )
    pos = p0[None, :, None, :] + vel[None, :, None, :] * t[..., None]
    pos += rng.normal(size=pos.shape) * jitter[None, :, None, None]
    # a daughter rides her mother's path, a gap off her side that opens
    # at the closing speed before and after the transfer
    away = np.maximum(
        np.maximum(t_on[None, :, None] - t[:, daughter], t[:, daughter] - t_off[None, :, None]),
        0.0,
    )
    sep = gap[None, :, None] + closing * away
    path = p0[None, mother, None, :] + vel[None, mother, None, :] * t[:, daughter, :, None]
    pos[:, daughter] = (
        path + side[None, :, None, :] * sep[..., None]
        + rng.normal(size=path.shape) * jitter[None, daughter, None, None]
    )

    flat = live.reshape(-1, slots)
    pings = flat.sum(axis=1)
    w_of = np.arange(windows)
    planted = np.concatenate([
        np.column_stack([np.arange(f, f + d), np.full(d, m), np.full(d, dg)])
        for f, d, m, dg in zip(first_w, np.minimum(dur_w, windows - first_w),
                               mother, daughter)
    ]) if k else np.zeros((0, 3), np.int64)
    return {
        "xy": pos.reshape(-1, slots, 2)[flat],
        "offsets": np.concatenate([[0], np.cumsum(pings)]).astype(np.int64),
        "window": np.repeat(w_of, n).astype(np.int64),
        "vessel": np.tile(np.arange(n), windows).astype(np.int64),
        "pings": pings.astype(np.int64),
        "radius": float(fleet["buffer_m"]) * one_m * (slots / pings),
        "planted": planted.astype(np.int64),
    }
