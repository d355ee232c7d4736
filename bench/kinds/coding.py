"""The coding deployment: a stream of planted samples, one per request,
coded (and with learning on, learned) against a unit-norm Gaussian W0, and
judged by the plain exact_fista reference of the sparse_svd dual.

A kind is everything that is a deployment's own; `bench/run.py` finds it
as `bench/kinds/<kind>.py` from the configuration's `kind` (this one when
there is none) and calls:

  FOLLOWS                    "task" and the engine (`DistConfig`) options
                             whose arithmetic this kind's reference follows,
                             each with the values it follows (None: any); a
                             configuration that sets another value, or any
                             other engine option away from its default, is
                             refused before a run
  w0(cfg, mesh, seed)        a function that makes W0 on the device, in the
                             engine's sharding, the same for the same seed
  requests(cfg, mix, seed)   an endless iterator of requests, each due at
                             one moment
  samples(cfg, request)      (optional) the request's n >= 1 samples, the
                             (n, M) float32 rows the engine codes; without
                             it a request is its own samples.  The harness
                             takes them where it draws the request, ahead of
                             its due time (prefill, the backlog's cap),
                             and again after the window (`fit_rows_foreign`)
  submit(svc, request)       (optional) hands one request to the service and
                             returns either one future per sample, in the
                             order of its samples, or one
                             `concurrent.futures.Future` for the whole
                             request, which the harness times as one: one
                             callback, its due, submit and done times and
                             micro-batch given to each of its samples.
                             It is called once the service's queue has room
                             for the request's samples, under the probe's
                             gate.  Without it the harness submits the
                             samples one by one (`svc.submit`)
  pick(cfg, served, rng)     after the window: which of the served `Served`
                             entries (bench/run.py) the check compares, read
                             to the host, and how many samples it wanted but
                             could not have.  An entry is a sample, or where
                             `submit` gave one future a whole request (`pos`
                             0, `x` the request as sent)
  check(cfg, seed, W0_fn, picked, fits, cols, digests)
                             the numbers compared against cfg["limits"]
  control(cfg, mix, seed, W0_fn, cols, follow, matmul)
                             (optional) the same numbers with the reference,
                             at a lower precision, in the program's place

Samples follow the planted sparse-code model of the program's synthetic
stream (a copy of its recipe, not an import): x = sum_s c_s a_{j_s} + noise,
with `sparsity` planted atoms a_j drawn from a K-atom dictionary that is
generated atom by atom from (seed, j), coefficients uniform in [0.5, 1.5]
with random signs, and Gaussian noise.  Sample i is the i-th draw of one
seeded stream, so a seed fixes every sample, and every seed gives samples
of the same size and the same count of planted atoms.

The check follows the program's first fit steps with the reference and
re-solves CHECK_ROWS served samples drawn from the seed, each against the
dictionary version it was coded with: `nu_gap`, `y_gap` (per sample
||served - ref|| / ||ref||) and `w_change_gap` (reference.change_gap).
"""

from __future__ import annotations

from typing import Callable, Dict, Iterator

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

import reference
from traffic import seed_words

FOLLOWS = {"task": {"sparse_svd"}, "mode": {"exact_fista"}, "iters": None}
CHECK_ROWS = 64  # served samples re-solved by the reference


class Stream:
    """Samples of one seeded planted stream, drawn one at a time in order."""

    def __init__(self, m: int, atoms: int, mix: dict, seed: int):
        self.m, self.atoms = m, atoms
        self.sparsity = int(mix["sparsity"])
        self.noise = float(mix["noise"])
        self.seed = seed % 2**63
        self._rng = np.random.default_rng(seed_words(seed, 1))

    def atom(self, j: int) -> np.ndarray:
        """Planted atom j, unit norm, from its own stream (seed, j)."""
        a = np.random.default_rng(seed_words(self.seed, 2, j)).standard_normal(
            self.m, dtype=np.float32)
        return a / np.linalg.norm(a)

    def next(self) -> np.ndarray:
        rng = self._rng
        idx = set()
        while len(idx) < self.sparsity:
            idx.add(int(rng.integers(self.atoms)))
        sign = rng.choice(np.array([-1.0, 1.0], np.float32), self.sparsity)
        coef = rng.uniform(0.5, 1.5, self.sparsity).astype(np.float32) * sign
        x = self.noise * rng.standard_normal(self.m, dtype=np.float32)
        for c, j in zip(coef, sorted(idx)):
            x += c * self.atom(j)
        return x.astype(np.float32)


def _key(seed: int, tag: int) -> jax.Array:
    word = seed_words(seed, tag).generate_state(1)[0]
    return jax.random.PRNGKey(int(word))


def w0(cfg: dict, mesh, seed: int) -> Callable:
    """W0 on the device in one jitted call from the seed: unit-norm Gaussian
    atoms, float32, in the engine's W sharding (atoms over `model`)."""
    m, k = cfg["m"], cfg["atoms"]
    sharding = NamedSharding(mesh, P(None, "model"))

    @jax.jit
    def draw(key):
        W = jax.random.normal(key, (m, k), jnp.float32)
        W = W / jnp.maximum(jnp.linalg.norm(W, axis=0, keepdims=True), 1e-12)
        return jax.lax.with_sharding_constraint(W, sharding)

    return lambda: draw(_key(seed, 4))


def requests(cfg: dict, mix: dict, seed: int) -> Iterator[np.ndarray]:
    """One planted sample per request."""
    stream = Stream(cfg["m"], cfg["atoms"], mix, seed)
    while True:
        yield stream.next()[None]


def pick(cfg: dict, served: list, rng: np.random.Generator):
    """CHECK_ROWS served samples drawn from the seed, grouped by the
    dictionary version they were coded against: {v: {"x", "nu", "y"}}."""
    chosen = sorted(rng.choice(len(served), min(CHECK_ROWS, len(served)), replace=False)
                    ) if served else []
    sampled = {}
    for j in chosen:
        s = served[j]
        nu, y = s.future.result()
        group = sampled.setdefault(s.version, {"x": [], "nu": [], "y": []})
        group["x"].append(s.x)
        group["nu"].append(np.asarray(nu))
        group["y"].append(np.asarray(y))
    sampled = {v: {k: np.stack(a) for k, a in g.items()} for v, g in sampled.items()}
    return sampled, CHECK_ROWS - len(chosen)


def check(cfg: dict, seed: int, W0_fn, sampled: Dict[int, dict], fits: list,
          cols: np.ndarray, digests: list) -> Dict[str, float]:
    """Follow the program's first fits with the reference, and compare.

    `sampled` is `pick`'s.  `fits` holds the input of the program's fit
    steps 1..F as the engine got it (padded); the reference fits the real
    rows it finds there, mean over their count.  `digests` the program's
    W[:, cols] after each of them (index 0 is W0's).  Samples are solved
    CHECK_ROWS at a time (one compiled shape).  Returns the numbers
    compared, each the worst over its set."""
    args = reference.solver_args(cfg, "highest")
    W = W0_fn()
    w0_cols = np.asarray(W[:, cols])
    out = {"nu_gap": 0.0, "y_gap": 0.0}
    n_follow = len(fits)
    for v in range(n_follow + 1):
        if v in sampled:
            s = sampled[v]
            n = len(s["x"])
            nu, y = reference.solve(W, jnp.asarray(reference.pad(s["x"], CHECK_ROWS)), **args)
            out["nu_gap"] = max(out["nu_gap"], reference.row_gap(s["nu"], np.asarray(nu)[:n]))
            out["y_gap"] = max(out["y_gap"], reference.row_gap(s["y"], np.asarray(y)[:n]))
        if v < n_follow:
            xb = fits[v]
            real = reference.real_rows(xb)
            W = reference.fit(W, jnp.asarray(reference.pad(real, len(xb))), float(len(real)),
                              cfg["mu_w"], **args)
    if n_follow:
        gap = reference.change_gap(w0_cols, digests[n_follow], np.asarray(W[:, cols]))
        out["w_change_gap"] = 1.0 if gap is None else gap
    return out


def control(cfg: dict, mix: dict, seed: int, W0_fn, cols: np.ndarray, follow: int,
            matmul: str = "high") -> Dict[str, float]:
    """The reference at `matmul` in the program's place, at the cell's
    sizes: the samples a run would re-solve and the batches of its first
    `follow` fit steps, drawn from the seed as a run draws them, then
    judged by `check` at float32 HIGHEST."""
    stream = Stream(cfg["m"], cfg["atoms"], mix, seed)
    per = CHECK_ROWS // (follow + 1)
    args = reference.solver_args(cfg, matmul)
    W = W0_fn()
    sampled, fits, digests = {}, [], [None]
    for v in range(follow + 1):
        x = np.stack([stream.next() for _ in range(per)])
        nu, y = reference.solve(W, jnp.asarray(reference.pad(x, CHECK_ROWS)), **args)
        sampled[v] = {"x": x, "nu": np.asarray(nu)[:per], "y": np.asarray(y)[:per]}
        if v < follow:
            xb = np.stack([stream.next() for _ in range(cfg["micro_batch"])])
            fits.append(xb)
            W = reference.fit(W, jnp.asarray(xb), float(cfg["micro_batch"]), cfg["mu_w"], **args)
            digests.append(np.asarray(W[:, cols]))
    del W
    return check(cfg, seed, W0_fn, sampled, fits, cols, digests)
