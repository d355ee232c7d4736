"""Runs of the harness on the CPU with the timed path broken underneath:
each fault a cell can have must turn `correct` false, and a sound run
must keep it true.  The chip check is skipped; everything after it runs."""

import jax
import jax.numpy as jnp
import pytest

import run
from helpers import tiny_config, tiny_run
from probe import EngineProbe



def part_filled_run(**kw):
    """Learning on a stream slow enough that micro-batches go out about
    half filled, so the service pads them and rescales the dictionary step."""
    return tiny_run("code.rate", cfg=tiny_config(micro_batch=64),
                    mix_over={"learn": True, "rate_per_s": 300.0}, **kw)


class Broken:
    """The engine with one fault planted; everything else passes through."""

    def __init__(self, coder, fault):
        self._inner, self._fault = coder, fault

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def solve(self, W, x, t0=0):
        nu, y = self._inner.solve(W, x, t0)
        if self._fault == "routing":  # each answer handed to another request
            return jnp.roll(nu, 1, axis=0), jnp.roll(y, 1, axis=0)
        if self._fault == "altered":  # every code changed where it is produced
            return nu, y * 1.001
        return nu, y

    def fit_batch(self, W, x, mu_w, t0=0):
        if self._fault == "unchanged":
            return W * 1.0
        if self._fault == "half_batch":  # half the batch left out, mean over the rest
            half = x.shape[0] // 2
            return self._inner.fit_batch(W, x.at[half:].set(0.0), 2.0 * mu_w, t0)
        return self._inner.fit_batch(W, x, mu_w, t0)


@pytest.mark.parametrize("mix", ["learn.backlog", "code.rate"])
def test_sound_run_is_correct(mix):
    out = tiny_run(mix)
    assert out["correct"], out["checks"]
    assert out["checks"]["samples_unchecked"]["value"] == 0


def test_sound_four_agent_run_is_correct():
    out = tiny_run("learn.backlog", model=4)
    assert out["correct"], out["checks"]


@pytest.mark.parametrize("fault,mix", [
    ("unchanged", "learn.backlog"),
    ("half_batch", "learn.backlog"),
    ("routing", "learn.backlog"),
    ("routing", "code.rate"),
    ("altered", "code.rate"),
])
def test_fault_is_not_correct(fault, mix):
    out = tiny_run(mix, wrap=lambda c: Broken(c, fault))
    assert not out["correct"], out["checks"]


def test_exchange_between_agents_left_out_is_not_correct(monkeypatch):
    from repro.runtime import dist

    monkeypatch.setattr(dist, "gossip_psum", lambda x, axis: x)
    out = tiny_run("learn.backlog", model=4)
    assert not out["correct"], out["checks"]


def test_sound_run_learning_on_part_filled_batches_is_correct():
    out = part_filled_run(entries=[{"name": "service.batch_fill", "unit": "%"}])
    assert out["correct"], out["checks"]
    assert out["metrics"]["service.batch_fill"]["value"] < 90


def above_the_probe(fault, mu_w):
    """A fault in the service, between it and the benchmark's probe: what
    the probe records is what the faulty service handed the engine."""

    class Faulty(EngineProbe):
        def fit_batch(self, W, x, mu_w_eff, t0=0):
            if fault == "unscaled":  # the mean over the padded rows, not the real ones
                mu_w_eff = mu_w
            if fault == "foreign":  # a row the client never sent is fitted
                x = x.at[0].add(1e-3)
            return super().fit_batch(W, x, mu_w_eff, t0)

    return Faulty


@pytest.mark.parametrize("fault,caught_by", [("unscaled", "w_change_gap"),
                                             ("foreign", "fit_rows_foreign")])
def test_service_fault_above_the_probe_is_not_correct(fault, caught_by, monkeypatch):
    monkeypatch.setattr(run, "EngineProbe", above_the_probe(fault, tiny_config()["mu_w"]))
    out = part_filled_run()
    assert not out["correct"], out["checks"]
    assert out["checks"][caught_by]["value"] > out["checks"][caught_by]["limit"]
