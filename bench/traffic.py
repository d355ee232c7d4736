"""The one traffic generator.  A mix is a data file `traffic/<name>.json`
that this module reads; nothing here knows a mix by name.

What a request holds comes from the configuration's kind
(`bench/kinds/<kind>.py`, which reads the rest of the mix: sizes, noise);
a request is n >= 1 samples due at one moment.  This module says when:

  * "poisson": an open loop at `rate_per_s` requests per second; request i
    is due at the i-th arrival of a seeded Poisson process, whether or not
    earlier ones are done.
  * "backlog": offered load above capacity; every request is due at the
    window's open and the client keeps `outstanding_batches` micro-batches'
    worth of samples submitted and not yet coded, so the service's queue
    never runs dry.
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


def seed_words(seed: int, *tag: int) -> np.random.SeedSequence:
    """The seed sequence of one stream drawn from the run's seed, by tag."""
    return np.random.SeedSequence([seed % 2**63, *tag])


def due_times(mix: dict, seed: int, seconds: float) -> np.ndarray:
    """Due times (s from the window's open) of the poisson arrivals in
    [0, seconds)."""
    rng = np.random.default_rng(seed_words(seed, 3))
    rate = float(mix["rate_per_s"])
    n = int(rate * seconds * 1.5) + 64
    t = np.cumsum(rng.exponential(1.0 / rate, n))
    while t[-1] < seconds:
        t = np.concatenate([t, t[-1] + np.cumsum(rng.exponential(1.0 / rate, n))])
    return t[t < seconds]
