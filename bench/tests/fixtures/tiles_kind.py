"""A toy image-denoising kind, which the harness's tests add as
`kinds/tiles.py` to show that a deployment plugs in by new files alone.

Images of `image` x `image` pixels, each cut into all its overlapping
`patch` x `patch` patches (column-major, as the paper stacks them), with
each patch's mean (its DC) removed; one image's patches are one request,
due together.  W0 is a fixed overcomplete 2-D DCT dictionary (the seed does
not enter).  The reference denoises each patch by the exact_fista dual as
x - nu, puts its DC back, and rebuilds the tile by averaging the patches
over each pixel; `tile_gap` is the worst tile's ||served - ref|| / ||ref||.
"""

from __future__ import annotations

import math
from typing import Callable, Dict, Iterator

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

import reference
from traffic import seed_words

FOLLOWS = {"task": {"sparse_svd"}, "mode": {"exact_fista"}, "iters": None}
CHECK_TILES = 2  # served tiles the reference rebuilds


def _image(cfg: dict, seed: int, r: int) -> np.ndarray:
    """Image r of the seed: three cosine gratings, an edge, and noise."""
    rng = np.random.default_rng(seed_words(seed, 7, r))
    yy, xx = np.mgrid[0:cfg["image"], 0:cfg["image"]] / cfg["image"]
    img = np.zeros_like(xx)
    for fy, fx, ph in rng.uniform(0.0, 3.0, (3, 3)):
        img += np.cos(2 * np.pi * (fy * yy + fx * xx) + ph)
    img += (xx > rng.uniform(0.3, 0.7)).astype(float)
    img += cfg["noise"] * rng.standard_normal(img.shape)
    return img.astype(np.float32)


def _patches(img: np.ndarray, p: int) -> np.ndarray:
    """All overlapping p x p patches, each stacked column by column."""
    win = np.lib.stride_tricks.sliding_window_view(img, (p, p))
    return win.transpose(0, 1, 3, 2).reshape(-1, p * p)


def _overlap_average(patches: np.ndarray, side: int, p: int) -> np.ndarray:
    """The image whose every pixel is the mean of the patches covering it."""
    acc = np.zeros((side, side))
    cnt = np.zeros((side, side))
    grid = side - p + 1
    for k, patch in enumerate(patches):
        i, j = divmod(k, grid)
        acc[i:i + p, j:j + p] += patch.reshape(p, p).T
        cnt[i:i + p, j:j + p] += 1.0
    return acc / cnt


def w0(cfg: dict, mesh, seed: int) -> Callable:
    """The overcomplete 2-D DCT: the outer products of n 1-D cosines of
    length `patch` (n^2 = atoms), AC atoms with their mean removed, unit
    norm, in the engine's W sharding."""
    p, n = cfg["patch"], math.isqrt(cfg["atoms"])
    if n * n != cfg["atoms"] or p * p != cfg["m"]:
        raise ValueError("tiles: atoms must be a square and m the patch's pixel count")
    sharding = NamedSharding(mesh, P(None, "model"))

    @jax.jit
    def make():
        d = jnp.cos(jnp.pi * jnp.arange(p)[:, None] * jnp.arange(n)[None, :] / n)
        d = d.at[:, 1:].add(-d[:, 1:].mean(axis=0))
        d = d / jnp.linalg.norm(d, axis=0)
        return jax.lax.with_sharding_constraint(jnp.kron(d, d).astype(jnp.float32), sharding)

    return make


def requests(cfg: dict, mix: dict, seed: int) -> Iterator[np.ndarray]:
    """Image r's DC-removed patches, r = 0, 1, 2, ..."""
    if mix["learn"]:
        raise ValueError("tiles: the toy kind does not learn")
    r = 0
    while True:
        x = _patches(_image(cfg, seed, r), cfg["patch"])
        yield (x - x.mean(axis=1, keepdims=True)).astype(np.float32)
        r += 1


def pick(cfg: dict, served: list, rng: np.random.Generator):
    """CHECK_TILES tiles drawn from the seed among those whose every patch
    was served: [{"request", "x", "nu"}], patches in the request's order."""
    n = (cfg["image"] - cfg["patch"] + 1) ** 2
    tiles: Dict[int, dict] = {}
    for s in served:
        tiles.setdefault(s.request, {})[s.pos] = s
    whole = sorted(r for r, got in tiles.items() if len(got) == n)
    chosen = sorted(rng.choice(len(whole), min(CHECK_TILES, len(whole)), replace=False)
                    ) if whole else []
    picked = []
    for j in chosen:
        got = tiles[whole[j]]
        picked.append({"request": whole[j], "x": np.stack([got[k].x for k in range(n)]),
                       "nu": np.stack([np.asarray(got[k].future.result()[0])
                                       for k in range(n)])})
    return picked, (CHECK_TILES - len(chosen)) * n


def check(cfg: dict, seed: int, W0_fn, picked: list, fits: list, cols, digests
          ) -> Dict[str, float]:
    W = W0_fn()
    args = reference.solver_args(cfg, "highest")
    side, p = cfg["image"], cfg["patch"]
    gap = 0.0
    for tile in picked:
        nu_ref, _ = reference.solve(W, jnp.asarray(tile["x"]), **args)
        dc = _patches(_image(cfg, seed, tile["request"]), p).mean(axis=1, keepdims=True)
        served = _overlap_average(tile["x"] - tile["nu"] + dc, side, p)
        ref = _overlap_average(tile["x"] - np.asarray(nu_ref) + dc, side, p)
        gap = max(gap, float(np.linalg.norm(served - ref) / np.linalg.norm(ref)))
    return {"tile_gap": gap}
