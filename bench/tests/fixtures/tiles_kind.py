"""A toy image-denoising kind, which the harness's tests add as
`kinds/tiles.py` to show that a deployment plugs in by new files alone.

A request is one noisy image of `image` x `image` pixels.  Its samples are
all its overlapping `patch` x `patch` patches (column-major, as the paper
stacks them), each with its mean (its DC) removed.  The service takes no
image yet, so `submit` cuts the patches, submits them one by one, and
answers the request with one future that resolves to the patches' nu once
the last of them is back.  W0 is a fixed overcomplete 2-D DCT dictionary
(the seed does not enter).  The reference draws the image again from the
seed, denoises each patch by the exact_fista dual as x - nu, puts its DC
back, and rebuilds the tile by averaging the patches over each pixel;
`tile_gap` is the worst tile's ||served - ref|| / ||ref||.
"""

from __future__ import annotations

import math
import threading
from concurrent.futures import Future
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


def _dc_removed(img: np.ndarray, p: int) -> np.ndarray:
    x = _patches(img, p)
    return (x - x.mean(axis=1, keepdims=True)).astype(np.float32)


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


def _gather(futs: list) -> Future:
    """One future for `futs`: the stack of their nu, in their order, once
    the last is back, or the first failure among them."""
    out, left, lock = Future(), [len(futs)], threading.Lock()

    def one(_):
        with lock:
            left[0] -= 1
            if left[0]:
                return
        try:
            out.set_result(np.stack([np.asarray(f.result()[0]) for f in futs]))
        except Exception as err:  # a patch failed: so does the tile
            out.set_exception(err)

    for f in futs:
        f.add_done_callback(one)
    return out


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
    """Image r, r = 0, 1, 2, ..."""
    if mix["learn"]:
        raise ValueError("tiles: the toy kind does not learn")
    r = 0
    while True:
        yield _image(cfg, seed, r)
        r += 1


def samples(cfg: dict, request: np.ndarray) -> np.ndarray:
    """The image's DC-removed patches."""
    return _dc_removed(request, cfg["patch"])


def submit(svc, request: np.ndarray) -> Future:
    """The image's DC-removed patches, each submitted as a sample; one
    future for them all."""
    return _gather(svc.submit_many(_dc_removed(request, math.isqrt(svc.sample_dim))))


def pick(cfg: dict, served: list, rng: np.random.Generator):
    """CHECK_TILES served tiles drawn from the seed: [{"request", "nu"}],
    nu of the patches in their order."""
    chosen = sorted(rng.choice(len(served), min(CHECK_TILES, len(served)), replace=False)
                    ) if served else []
    picked = [{"request": served[j].request, "nu": served[j].future.result()} for j in chosen]
    return picked, (CHECK_TILES - len(chosen)) * (cfg["image"] - cfg["patch"] + 1) ** 2


def check(cfg: dict, seed: int, W0_fn, picked: list, fits: list, cols, digests
          ) -> Dict[str, float]:
    W = W0_fn()
    args = reference.solver_args(cfg, "highest")
    side, p = cfg["image"], cfg["patch"]
    gap = 0.0
    for tile in picked:
        img = _image(cfg, seed, tile["request"])
        x, dc = _dc_removed(img, p), _patches(img, p).mean(axis=1, keepdims=True)
        nu_ref, _ = reference.solve(W, jnp.asarray(x), **args)
        served = _overlap_average(x - tile["nu"] + dc, side, p)
        ref = _overlap_average(x - np.asarray(nu_ref) + dc, side, p)
        gap = max(gap, float(np.linalg.norm(served - ref) / np.linalg.norm(ref)))
    return {"tile_gap": gap}
