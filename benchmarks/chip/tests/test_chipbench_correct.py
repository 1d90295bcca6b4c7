"""``correct``: sound runs pass, the lower-precision control fails, and
each fault a cell can have, planted under the timed path, fails.

The harness runs here on the CPU at n = 32 (Pallas in interpret mode),
with its look for a chip skipped; the comparison is the one the chip
runs make.
"""
import jax
import pytest

from chipbench_small import CELLS, run_small, small_cell

from benchmarks.chip import control
from repro.core import backend_pallas


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(name):
    r = run_small(name)
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert list(r)[-1] == "checks"
    assert set(r["metrics"]) == {m["name"] for m in small_cell(name).end_to_end}


@pytest.mark.parametrize("name", ["gemm_4096.tiled", "gaussian_4096.jitted",
                                  "gemm_4096.dse", "gaussian_4096.batched8"])
def test_control_fails_and_program_passes(name):
    cell = small_cell(name)
    for seed, (prog, ctl) in control.readings(cell, [1, 2, 3],
                                               log=lambda _m: None).items():
        for key, c in prog.items():
            assert c["value"] <= c["limit"], (seed, key, c)
            assert ctl[key]["value"] > ctl[key]["limit"], (seed, key, ctl)


def _alter_jitted(monkeypatch, alter):
    """Plant ``alter(out, arrays) -> out`` under ``PallasProgram.jitted``."""
    original = backend_pallas.PallasProgram.jitted

    def broken(self):
        run = original(self)
        return lambda arrays: alter(dict(run(arrays)), arrays)
    monkeypatch.setattr(backend_pallas.PallasProgram, "jitted", broken)


def _written(name):
    return "C" if name.startswith("gemm") else "out"


@pytest.mark.parametrize("name", ["gemm_4096.tiled", "gaussian_4096.jitted",
                                  "gemm_4096.dse"])
def test_an_answer_altered_where_produced_fails(monkeypatch, name):
    w = _written(name)

    def alter(out, arrays):
        out[w] = out[w].at[5, 7].add(1.0)
        return out
    _alter_jitted(monkeypatch, alter)
    assert not run_small(name)["correct"]


@pytest.mark.parametrize("name", ["gemm_4096.tiled", "gaussian_4096.jitted"])
def test_a_call_that_returns_its_inputs_unchanged_fails(monkeypatch, name):
    _alter_jitted(monkeypatch, lambda out, arrays: dict(arrays))
    assert not run_small(name)["correct"]


def test_half_the_batch_left_out_fails(monkeypatch):
    original = backend_pallas.BatchedRunner.__call__

    def broken(self, arrays):
        out = dict(original(self, arrays))
        half = out["out"].shape[0] // 2
        out["out"] = out["out"].at[half:].set(arrays["out"][half:])
        return out
    monkeypatch.setattr(backend_pallas.BatchedRunner, "__call__", broken)
    assert not run_small("gaussian_4096.batched8")["correct"]


def test_failing_calls_are_counted_and_not_correct(monkeypatch):
    original = backend_pallas.PallasProgram.jitted

    def broken(self):
        run, calls = original(self), []

        def wrapped(arrays):            # the warm-up call passes
            calls.append(1)
            if len(calls) > 1:
                raise RuntimeError("planted")
            return run(arrays)
        return wrapped
    monkeypatch.setattr(backend_pallas.PallasProgram, "jitted", broken)
    r = run_small("gaussian_4096.jitted")
    assert r["failed"] == r["attempted"] > 0
    assert not r["correct"]
    assert r["checks"]["calls_failed"]["value"] == r["failed"]


def test_same_seed_same_inputs_and_large_seeds():
    cell = small_cell("gemm_4096.tiled")
    from benchmarks.chip import harness
    a = harness.make_inputs(cell, 2**31 + 12345)
    b = harness.make_inputs(cell, 2**31 + 12345)
    c = harness.make_inputs(cell, 2**31 + 12346)
    assert len(a) == cell.traffic["input_sets"]
    assert all(bool((x["A"] == y["A"]).all()) for x, y in zip(a, b))
    assert not bool((a[0]["A"] == c[0]["A"]).all())
    assert not bool((a[0]["A"] == a[1]["A"]).all())
    assert jax.numpy.isfinite(a[0]["A"]).all()
