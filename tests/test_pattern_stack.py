"""The per-layer pattern stack, the published Mamba-2 mixer and the
one-pass prefill, on the CPU at reduced widths.

The mixer is held to a step-by-step recurrence written here from the
published equations; the prefill's cache to the cache teacher-forced
decode steps leave; the kernels' new arguments (groups of B/C in the
scan, a layer of a stack of caches in the decode kernel) to their
oracles.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_config, reduced
from repro.core import telemetry
from repro.kernels import ref
from repro.kernels.decode_attention import decode_attention_rows
from repro.kernels.ssm_scan import ssm_scan
from repro.models import (decode_step, forward, init_cache, init_params,
                          prefill)
from repro.models import mamba2 as M
from repro.models.model import pattern

F32_TOL = 1e-5   # float32 sums in another order: ~1e-7 relative, 100x room


def _cfg(arch="granite_4_0_h_micro", **over):
    return dataclasses.replace(reduced(get_config(arch)), **over)


def _recurrence(p, x, cfg):
    """The published Mamba-2 mixer, one token at a time, in numpy float64:
    conv over xBC with bias, SiLU, dt = softplus(dt + dt_bias), decay
    exp(dt A), h = decay h + B (x) (dt x), y = C . h + D x, then
    rmsnorm(y * silu(z)) per group and out_proj."""
    p = jax.tree_util.tree_map(lambda a: np.asarray(a, np.float64), p)
    x = np.asarray(x, np.float64)
    b, s, d = x.shape
    din, nh, ph, g, n = (cfg.ssm_inner, cfg.ssm_heads,
                         cfg.ssm_head_dim, M.GROUPS,
                         cfg.ssm_state)
    silu = lambda u: u / (1.0 + np.exp(-u))  # noqa: E731
    zxbcdt = x @ p["w_in"]
    z, xbc, dt = (zxbcdt[..., :din], zxbcdt[..., din:2 * din + 2 * g * n],
                  zxbcdt[..., 2 * din + 2 * g * n:])
    h = np.zeros((b, nh, n, ph))
    window = np.zeros((b, M.CONV_W, xbc.shape[-1]))
    out = np.zeros((b, s, d))
    for t in range(s):
        window = np.concatenate([window[:, 1:], xbc[:, t:t + 1]], axis=1)
        u = silu(np.einsum("bkc,kc->bc", window, p["conv"]) + p["conv_b"])
        xs = u[:, :din].reshape(b, nh, ph)
        bm = np.repeat(u[:, din:din + g * n].reshape(b, g, n), nh // g, 1)
        cm = np.repeat(u[:, din + g * n:].reshape(b, g, n), nh // g, 1)
        dtt = np.log1p(np.exp(dt[:, t] + p["dt_bias"]))
        decay = np.exp(-dtt * np.exp(p["a_log"]))
        h = decay[:, :, None, None] * h + \
            bm[:, :, :, None] * (dtt[:, :, None] * xs)[:, :, None, :]
        y = np.einsum("bhn,bhnp->bhp", cm, h) + xs * p["d_skip"][:, None]
        yg = (y.reshape(b, din) * silu(z[:, t])).reshape(b, g, -1)
        yg = yg / np.sqrt(np.mean(yg * yg, -1, keepdims=True) + cfg.norm_eps)
        out[:, t] = (yg.reshape(b, din) * p["norm"]["scale"]) @ p["w_out"]
    return out, h, window[:, 1:]


@pytest.mark.parametrize("groups", [1, 2])
@pytest.mark.parametrize("use_pallas", [False, True])
def test_mixer_matches_the_step_recurrence(groups, use_pallas, monkeypatch):
    # one group in every registered configuration; two hold the grouped
    # B/C path to the recurrence for a model that has them
    monkeypatch.setattr(M, "GROUPS", groups)
    cfg = _cfg(use_pallas=use_pallas)
    p = M.mamba2_init(jax.random.key(0), cfg)
    x = jax.random.normal(jax.random.key(1), (2, 64, cfg.d_model))
    want, h, window = _recurrence(p, x, cfg)
    got, state = jax.jit(lambda p, x: M.mamba2_apply(p, x, cfg))(p, x)
    scale = np.max(np.abs(want))
    assert np.max(np.abs(np.asarray(got) - want)) / scale < F32_TOL
    np.testing.assert_allclose(np.asarray(state["h"]), h.swapaxes(2, 3),
                               rtol=F32_TOL, atol=F32_TOL * np.max(np.abs(h)))
    np.testing.assert_allclose(np.asarray(state["conv"]), window, rtol=1e-6,
                               atol=1e-6)
    # one token at a time through the decode path, from the zero state
    st = M.mamba2_init_state(cfg, 2)
    step = jax.jit(lambda p, x, st: M.mamba2_decode(p, x, st, cfg))
    for t in range(x.shape[1]):
        o, st = step(p, x[:, t:t + 1], st)
        assert np.max(np.abs(np.asarray(o[:, 0]) - want[:, t])) / scale < F32_TOL


def test_padding_leaves_the_state_as_it_was():
    """Positions past ``length`` are padding: the state after them is the
    state after ``length`` tokens, and the outputs before are unchanged."""
    cfg = _cfg()
    p = M.mamba2_init(jax.random.key(2), cfg)
    x = jax.random.normal(jax.random.key(3), (2, 16, cfg.d_model))
    short, st_short = M.mamba2_apply(p, x[:, :11], cfg)
    long, st_long = M.mamba2_apply(p, x, cfg, length=11)
    np.testing.assert_allclose(np.asarray(long[:, :11]), np.asarray(short),
                               rtol=1e-5, atol=1e-6)
    for k in ("h", "conv"):
        np.testing.assert_allclose(np.asarray(st_long[k]),
                                   np.asarray(st_short[k]), rtol=1e-5,
                                   atol=1e-6)


def test_ssm_scan_kernel_shares_each_group_over_its_heads():
    rng = np.random.default_rng(4)
    b, s, h, g, pd, n = 2, 128, 4, 2, 16, 8
    x = jnp.asarray(rng.normal(size=(b, s, h, pd)), jnp.float32)
    a = jnp.asarray(rng.uniform(0.6, 1.0, size=(b, s, h)), jnp.float32)
    bb = jnp.asarray(rng.normal(size=(b, s, g, n)), jnp.float32)
    c = jnp.asarray(rng.normal(size=(b, s, g, n)), jnp.float32)
    y, hl = ssm_scan(x, a, bb, c, chunk=64, interpret=True)
    y_ref, h_ref = ref.ssm_scan(x, a, jnp.repeat(bb, h // g, 2),
                                jnp.repeat(c, h // g, 2))
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), rtol=1e-4,
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(hl), np.asarray(h_ref), rtol=1e-4,
                               atol=1e-4)


def test_decode_kernel_reads_one_layer_of_a_stack():
    rng = np.random.default_rng(5)
    lyr, b, hq, hkv, s, d = 3, 2, 4, 2, 256, 32
    q = jnp.asarray(rng.normal(size=(b, hq, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(lyr, b, hkv, s, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(lyr, b, hkv, s, d)), jnp.float32)
    rows = lambda c: c.transpose(0, 1, 3, 2, 4).reshape(lyr, b, s, hkv * d)  # noqa: E731
    length = jnp.asarray([100, 256], jnp.int32)
    got = decode_attention_rows(q, rows(k), rows(v), length=length,
                                layer=jnp.int32(1), scale=0.1, bkv=128,
                                interpret=True)
    want = ref.decode_attention(q, k[1], v[1], length=length, scale=0.1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5,
                               atol=1e-5)


def test_the_pattern_decides_which_layers_hold_kv():
    cfg = get_config("granite_4_0_h_micro")
    period, repeats = pattern(cfg)
    assert repeats == 4 and len(period) == 10
    assert [i for i, k in enumerate(cfg.layer_types) if k == "attention"] \
        == [5, 15, 25, 35]
    cache = jax.eval_shape(lambda: init_cache(cfg, 2, 256))
    for i, kind in enumerate(period):
        assert set(cache[f"l{i}"]) == ({"k", "v"} if kind == "attention"
                                       else {"h", "conv"})
        lead = jax.tree_util.tree_leaves(cache[f"l{i}"])[0].shape[:2]
        assert lead == (repeats, 2)
    assert cache["l5"]["k"].shape == (4, 2, 256, 8 * 64)
    assert cache["l0"]["h"].shape == (4, 2, 64, 64, 128)
    assert cache["l0"]["conv"].shape == (4, 2, 3, 2048 * 2 + 2 * 128)
    params = jax.eval_shape(lambda k: init_params(k, cfg), jax.random.key(0))
    for i, kind in enumerate(period):
        assert ("attn" in params["blocks"][f"l{i}"]) == (kind == "attention")
        assert ("mamba" in params["blocks"][f"l{i}"]) == (kind == "mamba")


def test_a_pattern_that_names_another_kind_is_refused():
    cfg = _cfg(layer_types=("mamba", "moe", "mamba", "moe"))
    with pytest.raises(ValueError, match="layer_types"):
        pattern(cfg)


def _teacher_forced(params, cfg, tokens, max_seq):
    cache = init_cache(cfg, tokens.shape[0], max_seq)
    step = jax.jit(lambda p, c, t, q: decode_step(p, cfg, c, t, q))
    for t in range(tokens.shape[1]):
        logits, cache = step(params, cache, tokens[:, t],
                             jnp.full((tokens.shape[0],), t, jnp.int32))
    return logits, cache


@pytest.mark.parametrize("arch", ["granite_4_0_h_micro", "smollm_360m"])
@pytest.mark.parametrize("use_pallas", [False, True])
def test_prefill_cache_equals_the_teacher_forced_cache(arch, use_pallas):
    """State, conv window and K/V rows after a one-pass prefill of 11
    tokens (padded to 128) are those 11 decode steps leave; the rows past
    the prompt stay as they were."""
    cfg = _cfg(arch, use_pallas=use_pallas)
    params = init_params(jax.random.key(6), cfg)
    tokens = jax.random.randint(jax.random.key(7), (2, 11), 0, cfg.vocab_size)
    last, want = _teacher_forced(params, cfg, tokens, 32)
    logits, got = jax.jit(lambda p, t, c: prefill(p, cfg, t, c))(
        params, tokens, init_cache(cfg, 2, 32))
    full, _ = forward(params, cfg, tokens=tokens)
    v = cfg.vocab_size
    np.testing.assert_allclose(np.asarray(logits[..., :v]),
                               np.asarray(full[..., :v]), rtol=F32_TOL,
                               atol=F32_TOL)
    np.testing.assert_allclose(np.asarray(logits[:, -1, :v]),
                               np.asarray(last[:, :v]), rtol=F32_TOL,
                               atol=F32_TOL)
    assert jax.tree_util.tree_structure(got) == \
        jax.tree_util.tree_structure(want)
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree_util.tree_leaves(want)):
        scale = float(jnp.max(jnp.abs(w))) or 1.0
        assert float(jnp.max(jnp.abs(g - w))) <= F32_TOL * scale, path
        if getattr(path[-1], "key", None) in ("k", "v"):
            assert float(jnp.max(jnp.abs(g[:, :, 11:]))) == 0.0, path


def test_prefill_fills_the_rows_from_start():
    """Two calls of two requests each fill a four-request cache as one
    call of four does."""
    cfg = _cfg()
    params = init_params(jax.random.key(8), cfg)
    tokens = jax.random.randint(jax.random.key(9), (4, 9), 0, cfg.vocab_size)
    run = jax.jit(lambda p, t, c, i: prefill(p, cfg, t, c, i)[1])
    whole = run(params, tokens, init_cache(cfg, 4, 16), 0)
    parts = run(params, tokens[:2], init_cache(cfg, 4, 16), 0)
    parts = run(params, tokens[2:], parts, 2)
    for a, b in zip(jax.tree_util.tree_leaves(whole),
                    jax.tree_util.tree_leaves(parts)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6,
                                   atol=1e-6)


def test_serve_prefills_in_one_call_and_records_the_cache():
    """``serve()`` runs the prefill as one call under one
    ``model.prefill`` span, and ``init_cache`` reports the cache's K/V
    and state bytes."""
    from repro.launch.serve import serve
    cfg = _cfg()
    params = init_params(jax.random.key(10), cfg)
    prompts = jax.random.randint(jax.random.key(11), (3, 12), 0,
                                 cfg.vocab_size)
    telemetry.start_trace("unused.json")
    try:
        res = serve(cfg, params, prompts, 3)
    finally:
        events = telemetry.stop_trace(export=False).events
    spans = [e for e in events if e["name"] == "model.prefill"]
    assert [s["args"] for s in spans] == [{"requests": 3, "tokens": 36}]
    (cache,) = [e["args"] for e in events if e["name"] == "model.cache"]
    period, reps = pattern(cfg)
    n_attn = reps * period.count("attention")
    n_mamba = reps * period.count("mamba")
    assert cache["kv_bytes"] == n_attn * 2 * 3 * cfg.num_kv_heads * 128 \
        * cfg.resolved_head_dim * 4
    assert cache["state_bytes"] == n_mamba * 3 * 4 * (
        cfg.ssm_heads * cfg.ssm_state * cfg.ssm_head_dim
        + (M.CONV_W - 1) * (cfg.ssm_inner + 2 * M.GROUPS * cfg.ssm_state))
    assert res.logits.shape == (3, 14, cfg.padded_vocab_size)
