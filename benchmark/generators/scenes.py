"""The raster scenes, made by the benchmark (they are the deployment's
input, as a model's weights are): MCD43A4-shaped single-band int16 arrays
— scaled reflectance 0..10,000, ``nodata`` where a cloud hid the ground —
laid north-up over a bounding box. Only numpy: the plain reference gets
the same arrays and the same geotransform, from no code of the program.

What every seed shares (``layout_seed``): the ground, a smooth reflectance
field, and the clouds' number and sizes, so that every seed folds the same
share of pixels. What ``seed`` draws: each scene's cloud places and its
per-pixel noise. The cloud mask is the top ``1 - valid_share`` of a field
of overlapping Gaussian blobs, so the valid share is exact on every seed.
"""

from __future__ import annotations

import numpy as np


def north_up(bbox, height: int, width: int, dtype=np.float64) -> tuple:
    """The GDAL geotransform ``(x0, sx, 0, y0, 0, sy)`` of a north-up
    ``height`` x ``width`` raster laid exactly over ``bbox`` (xmin, ymin,
    xmax, ymax). ``dtype`` rounds each term (the lower-precision control
    places pixel centres from float32 terms)."""
    xmin, ymin, xmax, ymax = (float(v) for v in bbox)
    gt = (xmin, (xmax - xmin) / width, 0.0, ymax, 0.0, -(ymax - ymin) / height)
    return tuple(float(np.asarray(v, dtype=dtype)) for v in gt)


def _blobs(height, width, places, sizes) -> np.ndarray:
    """Sum of axis-aligned Gaussian blobs, each the outer product of a
    row profile and a column profile (a 2400 x 2400 field in ~10 ms a
    blob)."""
    rows = np.arange(height, dtype=np.float32)[:, None]
    cols = np.arange(width, dtype=np.float32)[None, :]
    out = np.zeros((height, width), dtype=np.float32)
    for (cy, cx), (sy, sx) in zip(places, sizes):
        out += np.exp(-0.5 * ((rows - cy * height) / (sy * height)) ** 2) * \
            np.exp(-0.5 * ((cols - cx * width) / (sx * width)) ** 2)
    return out


def make_scenes(params: dict, shape, seed: int) -> list:
    """``params["pool_scenes"]`` arrays ``(height, width)`` int16. Keys:
    ``valid_share``, ``nodata``, ``value_range`` [lo, hi], ``noise``
    (per-pixel, +-), ``clouds`` (blobs a scene), ``cloud_size`` [lo, hi]
    (a blob's sigma as a share of the scene's side), ``layout_seed``."""
    height, width = (int(v) for v in shape)
    lo, hi = (int(v) for v in params["value_range"])
    nodata = int(params["nodata"])
    if lo <= nodata <= hi:
        raise ValueError(f"nodata {nodata} lies inside value_range {lo}..{hi}")
    layout = np.random.default_rng(int(params["layout_seed"]))
    n_clouds = int(params["clouds"])
    sizes = layout.uniform(*params["cloud_size"], size=(n_clouds, 2))
    # the ground: a few broad blobs over a mid-range floor
    ground = _blobs(height, width,
                    layout.uniform(0, 1, size=(12, 2)),
                    layout.uniform(0.1, 0.3, size=(12, 2)))
    ground = lo + (hi - lo) * (0.15 + 0.6 * ground / ground.max())
    rng = np.random.default_rng([int(seed), 0x5CE7E5])
    noise = int(params["noise"])
    scenes = []
    for _ in range(int(params["pool_scenes"])):
        cloud = _blobs(height, width,
                       rng.uniform(0, 1, size=(n_clouds, 2)), sizes)
        cut = np.quantile(cloud, float(params["valid_share"]))
        values = ground + rng.integers(
            -noise, noise + 1, size=ground.shape, dtype=np.int16)
        scene = np.clip(values, lo, hi).astype(np.int16)
        scene[cloud > cut] = nodata
        scenes.append(scene)
    return scenes
