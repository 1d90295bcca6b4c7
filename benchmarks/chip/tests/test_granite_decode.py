"""The granite-4.0-h-micro decode program (``configs/granite_4_0_h_micro.py``)
at the widths of ``reduced(granite_4_0_h_micro)``, on the CPU.

Its plain reference is held to the SSD recurrence and to the model's own
forward pass; the model's prefill and decode through the cache are held
to the reference at float32, tightly enough that bfloat16 fails; the
benchmark's ``correct`` passes sound runs, fails the control and fails
each fault planted under the timed path.
"""
import dataclasses
import json
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.models
import granite_faults
from chipbench_small import ROOT, run_small

from benchmarks.chip import control, harness

CELL = "granite_4_0_h_micro.decode_32x4k"
SEEDS = [2**33 + 17, 3, 2**31 + 101]
# float32 through two orders of summation (the model's prefill, scan and
# decode step against the reference's quadratic forms): ~2e-7 relative
# at these widths, so 50x room; bfloat16 weights and activations read
# ~2e-3, 100x over it
F32_TOL = 1e-5


def small_cell(dtype="bfloat16", requests=4, context=40, **sizes):
    """The cell at the widths of ``reduced(granite_4_0_h_micro)`` (a Mamba
    and an attention layer, twice over), in ``dtype``; ``sizes``
    overrides configuration keys."""
    from repro.configs.base import get_config, reduced
    cell = harness.resolve(harness.load_spec(), CELL)
    cfg = reduced(get_config("granite_4_0_h_micro"))
    config = dict(
        cell.config, dtype=dtype, num_hidden_layers=cfg.num_layers,
        layer_types=list(cfg.layer_types), hidden_size=cfg.d_model,
        num_attention_heads=cfg.num_heads,
        num_key_value_heads=cfg.num_kv_heads,
        shared_intermediate_size=cfg.d_ff, vocab_size=cfg.vocab_size,
        mamba_n_heads=cfg.ssm_heads, mamba_d_head=cfg.ssm_head_dim,
        mamba_d_state=cfg.ssm_state,
        assumed=dict(cell.config["assumed"], head_dim=cfg.resolved_head_dim))
    config.update(sizes)
    cell.config = config
    cell.traffic = dict(cell.traffic, requests=requests, context=context)
    return cell


def test_the_configuration_is_the_registered_model():
    """The configuration file's sizes and multipliers are those of
    ``granite_4_0_h_micro`` as registered, and the cell is one chip."""
    from repro.configs.base import get_config
    cell = harness.resolve(harness.load_spec(), CELL)
    got = cell.program._model_config(cell.config)
    assert dataclasses.replace(got, use_pallas=False) == \
        get_config("granite_4_0_h_micro")
    assert cell.chips == 1 and cell.builds and cell.lanes == 1
    assert cell.traffic == {"loop": "closed", "callers": 1, "requests": 32,
                            "context": 4096, "input_sets": 1}
    assert {m["name"] for m in cell.end_to_end} == {"step_ms", "setup_s"}
    assert {m["name"] for m in cell.per_layer} == {
        "prefill_s", "decode_attn_roofline", "fusion_ms", "idle_pct",
        "step_mfu_pct"}
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    (entry,) = [c for c in spec["configs"] if c["name"] == "granite_4_0_h_micro"]
    assert entry["reduced"] == cell.config["reduced"] == []


def _recurrence(xs, dt, a_log, bm, cm):
    """h_t = exp(dt_t A) h_{t-1} + B_t (x) dt_t x_t, y_t = C_t . h_t, one
    step at a time in float64."""
    xs, dt, a_log, bm, cm = (np.asarray(a, np.float64)
                             for a in (xs, dt, a_log, bm, cm))
    r, t, nh, ph = xs.shape
    per = nh // bm.shape[2]
    bm, cm = np.repeat(bm, per, 2), np.repeat(cm, per, 2)
    h = np.zeros((r, nh, bm.shape[-1], ph))
    y = np.zeros_like(xs)
    for i in range(t):
        decay = np.exp(-dt[:, i] * np.exp(a_log))
        h = decay[..., None, None] * h + \
            bm[:, i, :, :, None] * (dt[:, i, :, None] * xs[:, i])[:, :, None]
        y[:, i] = np.einsum("rhn,rhnp->rhp", cm[:, i], h)
    return y


def test_ssd_quadratic_form_matches_the_recurrence():
    prog = harness.resolve(harness.load_spec(), CELL).program
    rng = np.random.default_rng(0)
    r, t, nh, ph, g, n = 2, 48, 12, 8, 3, 16         # 12 heads: 2 blocks
    xs = rng.normal(size=(r, t, nh, ph))
    dt = rng.uniform(1e-3, 0.3, size=(r, t, nh))
    a_log = np.log(rng.uniform(1.0, 16.0, size=nh))
    bm, cm = rng.normal(size=(2, r, t, g, n))

    def dot(eq, x, y):
        return jnp.einsum(eq, x, y, precision=jax.lax.Precision.HIGHEST)
    got = prog.ssd(*(jnp.asarray(a, jnp.float32)
                     for a in (xs, dt, a_log, bm, cm)), dot)
    want = _recurrence(xs, dt, a_log, bm, cm)
    assert np.max(np.abs(np.asarray(got) - want)) / np.max(np.abs(want)) \
        < F32_TOL


@pytest.mark.parametrize("seed", SEEDS)
def test_reference_matches_the_model_forward_at_f32(seed):
    from repro.configs.base import get_config, reduced
    cell = small_cell(dtype="float32")
    (a,) = harness.make_inputs(cell, seed)
    cfg = reduced(get_config("granite_4_0_h_micro"))
    tokens = jnp.concatenate([a["prompt"], a["token"][:, None]], axis=1)
    logits, _ = repro.models.forward(a["params"], cfg, tokens=tokens)
    want = logits[:, -1, :cell.config["vocab_size"]]
    got = cell.program.reference(a, cell.config)["logits"]
    assert got.shape == want.shape
    assert harness.max_rel_err(got, want) < F32_TOL


def _entry_error(cell, seed):
    sets = harness.make_inputs(cell, seed)
    got = cell.program.build(cell.config, cell.traffic, sets)(sets[0])
    ref = cell.program.reference(sets[0], cell.config)
    return harness.max_rel_err(got["logits"], ref["logits"])


@pytest.mark.parametrize("seed", SEEDS)
def test_prefill_then_decode_matches_the_reference(seed):
    """The one-pass prefill fills the cache and a decode step reads it:
    the step's logits are the reference's full forward's last row."""
    assert _entry_error(small_cell(dtype="float32"), seed) < F32_TOL


def test_bfloat16_fails_the_float32_tolerance():
    assert _entry_error(small_cell(), SEEDS[0]) > 10 * F32_TOL


def test_sound_run_is_correct():
    r = run_small(cell=small_cell())
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert set(r["checks"]) == {"max_rel_err.logits",
                                "window_without_output", "calls_failed"}


def test_control_fails_and_program_passes():
    cell = small_cell()
    for seed, (prog, ctl) in control.readings(cell, [1, 2, 3],
                                               log=lambda _m: None).items():
        for key, c in prog.items():
            assert c["value"] <= c["limit"], (seed, key, c)
            assert ctl[key]["value"] > ctl[key]["limit"], (seed, key, ctl)


def test_every_call_is_the_same_work():
    """The state each call reads is the prefill's, not the last call's:
    repeated calls give the same logits."""
    cell = small_cell(dtype="float32")
    sets = harness.make_inputs(cell, SEEDS[1])
    entry = cell.program.build(cell.config, cell.traffic, sets)
    first = entry(sets[0])["logits"]
    for _ in range(2):
        assert harness.max_rel_err(entry(sets[0])["logits"], first) == 0.0


@pytest.mark.parametrize("fault", list(granite_faults.FAULTS))
def test_a_fault_in_the_step_fails(fault):
    # at the published state size: C . h, the state's part of each Mamba
    # output beside D x, grows with it; at the reduced 16 a zeroed state
    # moves the logits by 0.05-0.15 (seeds 7, 11, 2**33 + 5), at 128 by
    # 0.31-0.42
    cell = small_cell(mamba_d_state=128)
    with granite_faults.FAULTS[fault]():
        r = run_small(cell=cell)
    assert r["failed"] == 0
    assert not r["correct"], r["checks"]


def test_failing_calls_fail(monkeypatch):
    cell = small_cell()
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


def test_entry_decodes_as_serve_does():
    """The entry fills the cache through ``serve()``'s prefill and steps
    through ``decode_step``: fed the token ``serve()`` chose after the
    same prompts, its logits are the row ``serve()``'s decode step
    gives."""
    from repro.launch.serve import serve
    cell = small_cell(dtype="float32")
    sets = harness.make_inputs(cell, SEEDS[2])
    a = sets[0]
    served = serve(cell.program._model_config(cell.config), a["params"],
                   a["prompt"], gen=2)
    entry = cell.program.build(cell.config, cell.traffic, sets)
    got = entry(dict(a, token=served.tokens[:, 0]))["logits"]
    want = served.logits[:, -1, :cell.config["vocab_size"]]
    assert got.shape == want.shape
    assert harness.max_rel_err(got, want) < 1e-6


def test_prefill_spans_are_read_as_prefill_s():
    """``build`` prefills in calls of PREFILL_ROWS requests, each under a
    ``model.prefill`` span; ``prefill_s`` sums them."""
    from repro.core import telemetry
    cell = small_cell(requests=8)
    sets = harness.make_inputs(cell, SEEDS[0])
    telemetry.start_trace("unused.json")
    try:
        cell.program.build(cell.config, cell.traffic, sets)
    finally:
        events = telemetry.stop_trace(export=False).events
    spans = [e for e in events if e["name"] == "model.prefill"]
    rows = cell.program.PREFILL_ROWS
    assert [s["args"] for s in spans] == \
        [{"requests": rows, "tokens": rows * 39}] * (8 // rows)
    read = harness.load_module(harness.HERE / "metrics" / "prefill_s.py").read
    assert read(types.SimpleNamespace(spans=events)) == \
        pytest.approx(sum(s["dur"] for s in spans) * 1e-6)
    assert read(types.SimpleNamespace(spans=[])) is None
    (cache,) = [e["args"] for e in events if e["name"] == "model.cache"]
    assert cache["kv_bytes"] > 0 and cache["state_bytes"] > 0


def test_work_counts_the_model():
    cell = harness.resolve(harness.load_spec(), CELL)
    w = cell.program.work(cell.config, cell.traffic)
    cfg = cell.program._model_config(cell.config)
    shapes = jax.eval_shape(lambda k: repro.models.init_params(k, cfg),
                            jax.random.key(0))
    weights = sum(s.size * s.dtype.itemsize
                  for s in jax.tree_util.tree_leaves(shapes))
    kv = 32 * 4 * 2 * 8 * 64 * 4096 * 2                   # 1.07 GB of K/V
    state = 32 * 36 * 2 * 4 * (64 * 128 * 64 + 3 * 4352)  # read and written
    assert w["attention"]["bytes"] == kv
    assert w["ssm"]["bytes"] == state
    assert w["total"]["bytes"] == weights + kv + state + 32 * 100352 * 4
    assert w["total"]["flops"] > 2 * 32 * cfg.param_count()
    peaks = harness.peaks_for("TPU v5 lite")
    assert harness.roofline_s(w["total"], peaks) == pytest.approx(15.2e-3,
                                                                  rel=0.03)


def test_decode_attn_roofline_reads_the_kernel_time():
    read = harness.load_module(
        harness.HERE / "metrics" / "decode_attn_roofline.py").read
    work = {"attention": {"flops": 0, "bytes": 819e9 * 1e-3}}
    ctx = types.SimpleNamespace(
        work=work, peaks=harness.peaks_for("TPU v5 lite"), lanes=1, steps=10,
        trace=types.SimpleNamespace(kernel_s=10 * 4e-3))
    assert read(ctx) == pytest.approx(25.0)
    assert read(types.SimpleNamespace(**dict(vars(ctx), work={}))) is None
    ctx.trace.kernel_s = 0.0
    assert read(ctx) is None
