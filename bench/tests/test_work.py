"""bench/work.py against shapes counted by hand."""

import pytest

import work

CFG = {"m": 8192, "atoms": 65536, "mesh": [1, 1], "iters": 100, "dtype": "float32"}


def test_solve_and_fit_operations():
    # 100 iterations x (W^T nu and y W^T) x 2 B M K
    assert work.solve_flops(CFG, 256) == 100 * 2 * (2 * 256 * 8192 * 65536)
    # the fit is the atom update nu^T y alone: it takes the solve's duals
    assert work.fit_flops(CFG, 256) == 2 * 256 * 8192 * 65536
    assert work.fit_flops(dict(CFG, atoms=262144, mesh=[1, 4]), 64) == 2 * 64 * 8192 * 65536


def test_atoms_split_over_agents():
    four = dict(CFG, atoms=262144, mesh=[1, 4])
    assert work.atoms_per_chip(four) == 65536
    assert work.solve_flops(four, 64) == 4 * 100 * 64 * 8192 * 65536
    with pytest.raises(ValueError):
        work.atoms_per_chip(dict(CFG, atoms=10, mesh=[1, 4]))


def test_bytes_read_w_once_per_iteration():
    # W (8192 x 65536 float32) per iteration, plus nu and y of 256 samples
    assert work.solve_bytes(CFG, 256) == 100 * 8192 * 65536 * 4 + 256 * (8192 + 65536) * 4
    assert work.solve_bytes(dict(CFG, dtype="bfloat16"), 256) * 2 == work.solve_bytes(CFG, 256)


def test_least_time_names_its_bound():
    peak = work.peaks("TPU v5 lite")
    t, bound = work.least_time(work.solve_flops(CFG, 256), work.solve_bytes(CFG, 256), peak)
    assert bound == "compute"
    assert t == pytest.approx(work.solve_flops(CFG, 256) / 197e12)
    t, bound = work.least_time(1.0, 819e9, peak)
    assert (t, bound) == (1.0, "memory")


def test_unknown_chip_is_an_error():
    with pytest.raises(KeyError):
        work.peaks("cpu")
