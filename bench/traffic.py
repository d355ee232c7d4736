"""The one traffic generator.  A mix is a data file `traffic/<name>.json`
that this module reads; nothing here knows a mix by name.

Samples follow the planted sparse-code model of the program's synthetic
stream (a copy of its recipe, not an import): x = sum_s c_s a_{j_s} + noise,
with `sparsity` planted atoms a_j drawn from a K-atom dictionary that is
generated atom by atom from (seed, j), coefficients uniform in [0.5, 1.5]
with random signs, and Gaussian noise.  Sample i is the i-th draw of one
seeded stream, so a seed fixes every sample, and every seed gives samples
of the same size and the same count of planted atoms.

Arrivals:
  * "poisson": an open loop at `rate_per_s`; sample i is due at the i-th
    arrival of a seeded Poisson process, whether or not earlier ones are done.
  * "backlog": offered load above capacity; every sample is due at the
    window's open and the client keeps `outstanding_batches` micro-batches
    submitted and not yet coded, so the service's queue never runs dry.
"""

from __future__ import annotations

import json
import pathlib

import numpy as np

HERE = pathlib.Path(__file__).resolve().parent

ARRIVALS = ("poisson", "backlog")


def load(name: str, root: pathlib.Path = HERE) -> dict:
    """The mix `name` from `<root>/traffic/<name>.json`, checked."""
    mix = json.loads((root / "traffic" / f"{name}.json").read_text())
    if mix.get("arrivals") not in ARRIVALS:
        raise ValueError(f"traffic {name}: arrivals must be one of {ARRIVALS}")
    if mix["arrivals"] == "poisson" and not mix.get("rate_per_s", 0) > 0:
        raise ValueError(f"traffic {name}: a poisson mix needs rate_per_s > 0")
    if mix["arrivals"] == "backlog" and not mix.get("outstanding_batches", 0) >= 1:
        raise ValueError(f"traffic {name}: a backlog mix needs outstanding_batches >= 1")
    return mix


def _seed_words(seed: int, *tag: int) -> np.random.SeedSequence:
    return np.random.SeedSequence([seed % 2**63, *tag])


class Stream:
    """Samples of one seeded planted stream, drawn one at a time in order."""

    def __init__(self, m: int, atoms: int, mix: dict, seed: int):
        self.m, self.atoms = m, atoms
        self.sparsity = int(mix["sparsity"])
        self.noise = float(mix["noise"])
        self.seed = seed % 2**63
        self._rng = np.random.default_rng(_seed_words(seed, 1))

    def atom(self, j: int) -> np.ndarray:
        """Planted atom j, unit norm, from its own stream (seed, j)."""
        a = np.random.default_rng(_seed_words(self.seed, 2, j)).standard_normal(
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


def due_times(mix: dict, seed: int, seconds: float) -> np.ndarray:
    """Due times (s from the window's open) of the poisson arrivals in
    [0, seconds)."""
    rng = np.random.default_rng(_seed_words(seed, 3))
    rate = float(mix["rate_per_s"])
    n = int(rate * seconds * 1.5) + 64
    t = np.cumsum(rng.exponential(1.0 / rate, n))
    while t[-1] < seconds:
        t = np.concatenate([t, t[-1] + np.cumsum(rng.exponential(1.0 / rate, n))])
    return t[t < seconds]
