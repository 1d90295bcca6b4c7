"""Per-architecture smoke tests: REDUCED config of the same family, one
forward + one train-gradient step + a decode step on CPU; assert output
shapes and no NaNs.  Full configs are exercised only by the dry-run.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ARCH_IDS, all_configs, get_config, reduced
from repro.models import decode_step, forward, init_cache, init_params, loss_fn

ALL = list(all_configs().keys())


def _batch(cfg, b=2, s=16, key=0):
    rng = np.random.default_rng(key)
    batch = {"labels": jnp.asarray(rng.integers(0, cfg.vocab_size, (b, s)))}
    if cfg.frontend:
        batch["embeds"] = jnp.asarray(
            rng.normal(size=(b, s, cfg.d_model)), jnp.float32)
    else:
        batch["tokens"] = jnp.asarray(rng.integers(0, cfg.vocab_size, (b, s)))
    return batch


@pytest.mark.parametrize("arch", ALL)
def test_forward_shapes_no_nans(arch):
    cfg = reduced(get_config(arch))
    params = init_params(jax.random.key(0), cfg)
    batch = _batch(cfg)
    logits, aux = jax.jit(lambda p, b: forward(
        p, cfg, tokens=b.get("tokens"), embeds=b.get("embeds")))(params, batch)
    assert logits.shape == (2, 16, cfg.padded_vocab_size)
    assert not bool(jnp.any(jnp.isnan(logits)))
    assert not bool(jnp.isnan(aux))
    # pad-vocab logits are masked to -inf
    if cfg.padded_vocab_size > cfg.vocab_size:
        assert float(jnp.max(logits[..., cfg.vocab_size:])) < -1e29


@pytest.mark.parametrize("arch", ALL)
def test_train_step_grad_no_nans(arch):
    cfg = reduced(get_config(arch))
    params = init_params(jax.random.key(1), cfg)
    batch = _batch(cfg, key=1)

    @jax.jit
    def step(p, b):
        (loss, metrics), grads = jax.value_and_grad(
            lambda p: loss_fn(p, cfg, b), has_aux=True)(p)
        return loss, grads

    loss, grads = step(params, batch)
    assert np.isfinite(float(loss))
    leaves = jax.tree_util.tree_leaves(grads)
    assert leaves
    for g in leaves:
        assert not bool(jnp.any(jnp.isnan(g))), "NaN gradient"


@pytest.mark.parametrize("arch", ALL)
def test_decode_step_shapes(arch):
    cfg = reduced(get_config(arch))
    params = init_params(jax.random.key(2), cfg)
    b, max_seq = 2, 32
    cache = init_cache(cfg, b, max_seq)
    tok = jnp.array([1, 2], jnp.int32)
    pos = jnp.array([0, 0], jnp.int32)
    logits, cache2 = jax.jit(
        lambda p, c, t, q: decode_step(p, cfg, c, t, q))(params, cache, tok, pos)
    assert logits.shape == (b, cfg.padded_vocab_size)
    assert not bool(jnp.any(jnp.isnan(logits)))
    # cache structure preserved
    assert jax.tree_util.tree_structure(cache) == jax.tree_util.tree_structure(cache2)


@pytest.mark.parametrize("arch", ["smollm_360m", "zamba2_1_2b", "xlstm_1_3b",
                                  "granite_moe_1b", "granite_4_0_h_micro"])
def test_prefill_decode_consistency(arch):
    """Greedy decode after teacher-forced prefill must match full forward."""
    cfg = reduced(get_config(arch))
    params = init_params(jax.random.key(3), cfg)
    b, s = 1, 8
    rng = np.random.default_rng(3)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (b, s)))
    full_logits, _ = forward(params, cfg, tokens=tokens)

    cache = init_cache(cfg, b, 16)
    step = jax.jit(lambda p, c, t, q: decode_step(p, cfg, c, t, q))
    for t in range(s):
        logits, cache = step(params, cache, tokens[:, t], jnp.array([t]))
        np.testing.assert_allclose(
            np.asarray(logits), np.asarray(full_logits[:, t, :]),
            rtol=2e-2, atol=2e-2)


def test_param_counts_in_band():
    """Full configs must land near their nameplate sizes."""
    expect = {
        "starcoder2_7b": (6e9, 9e9),
        "codeqwen1_5_7b": (6e9, 9e9),
        "smollm_360m": (0.25e9, 0.5e9),
        "qwen2_72b": (65e9, 80e9),
        "musicgen_large": (1.5e9, 3.5e9),
        "zamba2_1_2b": (0.8e9, 1.8e9),
        "llama4_maverick_400b": (320e9, 480e9),
        "granite_moe_1b": (0.8e9, 1.8e9),
        "xlstm_1_3b": (0.8e9, 2.0e9),
        "phi3_vision_4_2b": (3.3e9, 5e9),
        "granite_4_0_h_micro": (3.0e9, 3.4e9),
    }
    for arch, (lo, hi) in expect.items():
        n = get_config(arch).param_count()
        assert lo <= n <= hi, f"{arch}: {n / 1e9:.2f}B not in [{lo / 1e9}, {hi / 1e9}]"


def test_moe_active_params():
    cfg = get_config("llama4_maverick_400b")
    active = cfg.active_param_count()
    assert 10e9 <= active <= 25e9, f"active {active / 1e9:.1f}B vs nameplate 17B"


@pytest.mark.parametrize("use_pallas", [False, True])
def test_serve_logits_match_forward(use_pallas):
    """The serve loop (prefill then greedy decode through the KV cache)
    gives, at every step, the logits a full forward gives over the same
    tokens; ``use_pallas`` runs the decode-attention kernel."""
    import dataclasses
    from repro.launch.serve import serve
    cfg = dataclasses.replace(reduced(get_config("smollm_360m")),
                              use_pallas=use_pallas)
    params = init_params(jax.random.key(4), cfg)
    rng = np.random.default_rng(4)
    prompts = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 6)), jnp.int32)
    res = serve(cfg, params, prompts, 4)
    assert res.logits.shape == (2, 9, cfg.padded_vocab_size)
    assert res.tokens.shape == (2, 4)
    seq = jnp.concatenate([prompts, res.tokens[:, :-1]], axis=1)
    ref, _ = forward(params, cfg, tokens=seq)
    np.testing.assert_allclose(np.asarray(res.logits), np.asarray(ref),
                               rtol=2e-2, atol=2e-2)
    np.testing.assert_array_equal(np.asarray(res.tokens[:, 1:]),
                                  np.asarray(jnp.argmax(ref[:, 6:], -1)))
