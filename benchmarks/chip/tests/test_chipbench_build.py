"""The harness's ``build`` hook: a program that builds its own entry, on a
model-stack decode program (``lm_decode_small.py``) at the widths of
``reduced(smollm_360m)``, and the unchanged path of the POM cells.

The decode program's reference is tied to the model's own forward pass;
its ``correct`` passes on sound runs, fails on the control, and fails on
each fault planted under the timed path.
"""
import jax
import jax.numpy as jnp
import pytest

import repro.models
from chipbench_small import (CELLS, LM_CELL, lm_spec, run_small,
                             small_cell, small_lm_cell)

from benchmarks.chip import control, harness

SEED = 2**33 + 17


def test_reference_matches_the_model_forward_at_f32():
    from repro.configs.base import get_config, reduced
    cell = small_lm_cell(dtype="float32")
    (a,) = harness.make_inputs(cell, SEED)
    cfg = reduced(get_config("smollm_360m"), num_kv_heads=2)
    tokens = jnp.concatenate([a["prompt"], a["token"][:, None]], axis=1)
    logits, _ = repro.models.forward(a["params"], cfg, tokens=tokens)
    want = logits[:, -1, :cell.config["vocab_size"]]
    got = cell.program.reference(a, cell.config)["logits"]
    assert got.shape == want.shape
    assert harness.max_rel_err(got, want) < 1e-5


def test_sound_run_is_correct():
    r = run_small(cell=small_lm_cell())
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert set(r["checks"]) == {"max_rel_err.logits",
                                "window_without_output", "calls_failed"}


def test_control_fails_and_program_passes():
    cell = small_lm_cell()
    for seed, (prog, ctl) in control.readings(cell, [1, 2, 3],
                                               log=lambda _m: None).items():
        for key, c in prog.items():
            assert c["value"] <= c["limit"], (seed, key, c)
            assert ctl[key]["value"] > ctl[key]["limit"], (seed, key, ctl)


def _plant_in_step(monkeypatch, cell, fault):
    """Plant ``fault(logits, cache, pos, last) -> (logits, cache, pos)``
    in the model's decode step; ``last`` is the prompt's length, the
    position the entry decodes at."""
    original = repro.models.decode_step
    last = cell.traffic["context"] - 1

    def broken(params, cfg, cache, token, pos):
        logits, cache, pos = fault(None, cache, pos, last)
        logits, cache = original(params, cfg, cache, token, pos)
        return fault(logits, cache, pos, last)[:2]
    monkeypatch.setattr(repro.models, "decode_step", broken)


def _wrong_position(logits, cache, pos, last):
    if logits is None:                     # before the step: one row back
        pos = jnp.where(pos >= last, pos - 1, pos)
    return logits, cache, pos


def _zeroed_cache(logits, cache, pos, last):
    if logits is None:
        at = pos[0] >= last
        cache = jax.tree_util.tree_map(
            lambda c: jnp.where(at, jnp.zeros_like(c), c), cache)
    return logits, cache, pos


def _half_the_requests(logits, cache, pos, last):
    if logits is not None:
        half = logits.shape[0] // 2
        keep = (jnp.arange(logits.shape[0]) < half) | (pos < last)
        logits = jnp.where(keep[:, None], logits, 0.0)
    return logits, cache, pos


@pytest.mark.parametrize("fault", [_wrong_position, _zeroed_cache,
                                   _half_the_requests],
                         ids=["wrong_position", "zeroed_cache",
                              "half_the_requests"])
def test_a_fault_in_the_step_fails(monkeypatch, fault):
    cell = small_lm_cell()
    _plant_in_step(monkeypatch, cell, fault)
    r = run_small(cell=cell)
    assert r["failed"] == 0
    assert not r["correct"], r["checks"]


def test_failing_calls_fail(monkeypatch):
    cell = small_lm_cell()
    build = cell.program.build

    def broken(config, traffic, sets):
        run, calls = build(config, traffic, sets), []

        def entry(arrays):              # the warm-up call passes
            calls.append(1)
            if len(calls) > 1:
                raise RuntimeError("planted")
            return run(arrays)
        return entry
    monkeypatch.setattr(cell.program, "build", broken)
    r = run_small(cell=cell)
    assert r["failed"] == r["attempted"] > 0
    assert not r["correct"]


def test_requests_are_not_lanes():
    cell = harness.resolve(lm_spec(), LM_CELL["name"])
    assert cell.builds and cell.lanes == 1
    assert "batch" not in cell.traffic and cell.traffic["requests"] == 64
    spec = lm_spec()
    spec["workloads"][-1]["traffic"] = "batched8"     # a mix with a batch
    with pytest.raises(ValueError, match="batch"):
        harness.resolve(spec, LM_CELL["name"])
    b8 = harness.resolve(harness.load_spec(), "gaussian_4096.batched8")
    assert not b8.builds and b8.lanes == 8


def test_work_of_the_decode_program_counts_the_model():
    from repro.configs.base import get_config
    cell = harness.resolve(lm_spec(), LM_CELL["name"])
    w = cell.program.work(cell.config, cell.traffic)
    params = get_config("smollm_360m").param_count()
    kv = 64 * 32 * 2 * 5 * 64 * 2048 * 2                 # 5.4 GB of cache
    assert w["attention"]["bytes"] == kv
    assert w["total"]["bytes"] == 2 * params + kv + 64 * 49152 * 4
    peaks = harness.peaks_for("TPU v5 lite")
    assert harness.roofline_s(w["total"], peaks) == pytest.approx(7.4e-3,
                                                                  rel=0.02)


@pytest.mark.parametrize("name", CELLS + [LM_CELL["name"]])
def test_set_up_order_of_each_kind_of_program(monkeypatch, name):
    """A POM cell compiles through ``pom.compile`` before its inputs are
    drawn, as before the hook; a build program's inputs are drawn first
    and handed to ``build``."""
    import repro.core.pipeline as pipeline
    order = []
    compile_, draw = pipeline.compile, harness.make_inputs

    def compiling(*a, **k):
        order.append("compile")
        return compile_(*a, **k)

    def drawing(*a, **k):
        order.append("inputs")
        return draw(*a, **k)
    monkeypatch.setattr(pipeline, "compile", compiling)
    monkeypatch.setattr(harness, "make_inputs", drawing)
    cell = small_lm_cell() if name == LM_CELL["name"] else small_cell(name)
    if cell.builds:
        build = cell.program.build

        def building(config, traffic, sets):
            order.append("build")
            assert len(sets) == traffic["input_sets"]
            return build(config, traffic, sets)
        monkeypatch.setattr(cell.program, "build", building)
    assert run_small(cell=cell)["correct"]
    assert order == (["inputs", "build"] if cell.builds
                     else ["compile", "inputs"])


def test_entry_decodes_as_serve_does():
    """The entry's step and prefill are ``serve()``'s: its logits are the
    last row ``serve()`` gives over prompt + token, so a change to
    ``serve()``'s decode path that the test program does not follow
    shows here."""
    from repro.launch.serve import serve
    cell = small_lm_cell(dtype="float32")
    sets = harness.make_inputs(cell, SEED)
    a = sets[0]
    got = cell.program.build(cell.config, cell.traffic, sets)(a)["logits"]
    tokens = jnp.concatenate([a["prompt"], a["token"][:, None]], axis=1)
    served = serve(cell.program._model_config(cell.config), a["params"],
                   tokens, gen=1)
    want = served.logits[:, -1, :cell.config["vocab_size"]]
    assert got.shape == want.shape
    assert harness.max_rel_err(got, want) < 1e-6


def test_step_ms_p95_is_the_tail_of_single_calls(monkeypatch):
    """One call in ten planted slow: the 95th percentile of the calls is
    a slow call, not an average over calls."""
    import time
    build, slow = harness.build_entry, 0.02

    def building(cell, sets=None):
        run, calls = build(cell, sets), []

        def entry(arrays):
            calls.append(1)
            if len(calls) % 10 == 0:
                time.sleep(slow)
            return run(arrays)
        return entry
    monkeypatch.setattr(harness, "build_entry", building)
    r = run_small("gaussian_4096.jitted", seconds=0.5)
    assert r["correct"] and r["attempted"] >= 40, r
    m = r["metrics"]
    assert m["step_ms_p95"]["value"] >= 1e3 * slow
    assert m["step_ms"]["value"] < 0.5 * m["step_ms_p95"]["value"]
