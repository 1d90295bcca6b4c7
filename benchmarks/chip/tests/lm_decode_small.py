"""A model-stack decode program that builds its own entry (``build``), for
the configurations that name ``"program": "lm_decode_small"``
(``lm_decode_small.json``). It is test data for the harness's ``build``
hook, not a cell of ``BENCHMARK.json``.

The model is a Llama-style dense stack (RMSNorm, RoPE, GQA causal
attention, SwiGLU, tied embeddings) served through the model stack's
own decode path. The traffic names ``requests`` and ``context``.

* ``inputs`` draws the weights, in the layout ``repro.models`` keeps and
  the type they are served in, each request's prompt of ``context - 1``
  tokens and its next token.
* ``build`` jits ``repro.models.decode_step`` with the cache donated, as
  ``launch/serve.py`` does, with the Pallas decode kernel wherever it
  compiles, and fills the cache by the teacher-forced prefill that
  ``serve()`` runs. The entry runs one decode step of every request at
  position ``context - 1`` and returns ``{"logits"}``; it re-writes the
  same cache row, so every call is the same work.
* ``reference`` is a full forward over prompt and token in plain
  ``jax.numpy`` at float32 ``"highest"``, in blocks of requests, and
  imports nothing of the program; ``control`` is the same with every
  matmul's operands rounded to float8_e4m3.
"""
import sys
import time

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
# the decode kernel walks the cache in blocks of 128 rows
CACHE_BLOCK = 128
# the model config keys the configuration file states
SIZES = ("num_layers", "d_model", "num_heads", "num_kv_heads", "head_dim",
         "d_ff", "vocab_size", "rope_theta", "norm_eps")
# bytes of attention scores one block of requests of the reference holds
REF_SCORE_BYTES = 1 << 29


def _dims(config):
    return (config["num_layers"], config["d_model"], config["num_heads"],
            config["num_kv_heads"], config["head_dim"], config["d_ff"],
            config["vocab_size"])


def _model_config(config):
    """The model stack's config of ``config["arch"]`` with the sizes and
    the type the configuration states."""
    import dataclasses
    from repro.configs.base import get_config
    from repro.runtime import pallas_interpret
    cfg = dataclasses.replace(
        get_config(config["arch"]), **{k: config[k] for k in SIZES},
        dtype=config["dtype"], param_dtype=config["dtype"],
        use_pallas=not pallas_interpret())
    if (cfg.family, cfg.tie_embeddings, cfg.mlp_gated, cfg.qkv_bias) != \
            ("dense", True, True, False):
        raise ValueError(f"{cfg.name}: the reference is a tied, gated, "
                         "bias-free dense stack")
    return cfg


def inputs(key, config, traffic):
    """Weights (``repro.models`` layout, the configuration's dtype; the
    embedding table padded to a multiple of 2048 rows as the model keeps
    it), ``prompt`` (requests, context - 1) and ``token`` (requests,)."""
    n_l, d, h, kv, hd, f, v = _dims(config)
    dt = jnp.dtype(config["dtype"])
    r, t = traffic["requests"], traffic["context"]
    ks = iter(jax.random.split(key, 16))

    def normal(shape, std):
        return (jax.random.normal(next(ks), shape, jnp.float32)
                * std).astype(dt)

    def scale(*shape):                   # norms near 1, not all ones
        return (1.0 + 0.1 * jax.random.normal(next(ks), shape,
                                              jnp.float32)).astype(dt)

    v_pad = -(-v // 2048) * 2048
    params = {
        "embed": {"tok": normal((v_pad, d), 0.02)},
        "final_norm": {"scale": scale(d)},
        "blocks": {
            "ln1": {"scale": scale(n_l, d)},
            "attn": {"wq": normal((n_l, d, h * hd), d ** -0.5),
                     "wk": normal((n_l, d, kv * hd), d ** -0.5),
                     "wv": normal((n_l, d, kv * hd), d ** -0.5),
                     "wo": normal((n_l, h * hd, d), (h * hd) ** -0.5)},
            "ln2": {"scale": scale(n_l, d)},
            "mlp": {"wi": normal((n_l, d, f), d ** -0.5),
                    "wg": normal((n_l, d, f), d ** -0.5),
                    "wo": normal((n_l, f, d), f ** -0.5)}}}
    return {"params": params,
            "prompt": jax.random.randint(next(ks), (r, t - 1), 0, v,
                                         jnp.int32),
            "token": jax.random.randint(next(ks), (r,), 0, v, jnp.int32)}


def build(config, traffic, sets):
    """The entry: one decode step of every request at position
    ``context - 1``, after the cache is filled from the prompt."""
    import repro.models as models
    if len(sets) != 1:
        raise ValueError("the cache holds one input set's prompt")
    cfg = _model_config(config)
    a = sets[0]
    want = jax.eval_shape(lambda k: models.init_params(k, cfg),
                          jax.random.key(0))
    got = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), a["params"])
    if got != want:
        raise ValueError("inputs() drew weights the model does not take")
    b, plen = a["prompt"].shape
    max_seq = -(-(plen + 1) // CACHE_BLOCK) * CACHE_BLOCK
    step = jax.jit(lambda p, c, t, q: models.decode_step(p, cfg, c, t, q),
                   donate_argnums=(1,))
    cache = models.init_cache(cfg, b, max_seq)
    cols = a["prompt"].T              # cols[t]: every request's t-th token
    pos = jnp.zeros((b,), jnp.int32)
    t0 = time.perf_counter()
    for t in range(plen):             # serve()'s teacher-forced prefill
        _, cache = step(a["params"], cache, cols[t], pos + t)
    jax.block_until_ready(cache)
    print(f"lm_decode_small: prefill of {plen} steps x {b} requests "
          f"{time.perf_counter() - t0:.3f} s", file=sys.stderr, flush=True)
    state = {"cache": cache}
    at = pos + plen
    vocab = config["vocab_size"]

    def entry(arrays):
        logits, state["cache"] = step(arrays["params"], state["cache"],
                                      arrays["token"], at)
        return {"logits": logits if logits.shape[-1] == vocab
                else logits[:, :vocab]}
    return entry


def _identity(x):
    return x


def _fp8(x):
    return jax.lax.reduce_precision(x, exponent_bits=4, mantissa_bits=3)


def _forward_last(p, tokens, config, q):
    """Logits (r, vocab) at the last position of ``tokens`` (r, T), float32,
    every matmul's operands passed through ``q``."""
    n_l, d, h, kv, hd, f, v = _dims(config)
    eps, theta = config["norm_eps"], config["rope_theta"]
    r, t = tokens.shape
    g = h // kv

    def dot(eq, x, y):
        return jnp.einsum(eq, q(x), q(y), precision=HIGHEST)

    def norm(x, s):
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * s

    half = hd // 2
    ang = (jnp.arange(t, dtype=jnp.float32)[:, None]
           / theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    cos, sin = jnp.cos(ang), jnp.sin(ang)           # (T, half)

    def rope(x):                                # (r, T, heads..., hd)
        x1, x2 = x[..., :half], x[..., half:]
        at = (t,) + (1,) * (x.ndim - 3) + (half,)
        c, s = cos.reshape(at), sin.reshape(at)
        return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)

    causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]

    def layer(x, w):
        y = norm(x, w["ln1"])
        qh = rope(dot("rtd,de->rte", y, w["wq"]).reshape(r, t, kv, g, hd))
        kh = rope(dot("rtd,de->rte", y, w["wk"]).reshape(r, t, kv, hd))
        vh = dot("rtd,de->rte", y, w["wv"]).reshape(r, t, kv, hd)
        s = dot("rqkgd,rskd->rkgqs", qh, kh) / jnp.sqrt(jnp.float32(hd))
        s = jnp.where(causal, s, -jnp.inf)
        o = dot("rkgqs,rskd->rqkgd", jax.nn.softmax(s, -1), vh)
        x = x + dot("rte,ed->rtd", o.reshape(r, t, h * hd), w["wo"])
        y = norm(x, w["ln2"])
        m = jax.nn.silu(dot("rtd,df->rtf", y, w["wg"])) \
            * dot("rtd,df->rtf", y, w["wi"])
        return x + dot("rtf,fd->rtd", m, w["wo2"]), None

    f32 = lambda x: x.astype(jnp.float32)  # noqa: E731
    bl = p["blocks"]
    ws = {"ln1": bl["ln1"]["scale"], "ln2": bl["ln2"]["scale"],
          "wq": bl["attn"]["wq"], "wk": bl["attn"]["wk"],
          "wv": bl["attn"]["wv"], "wo": bl["attn"]["wo"],
          "wi": bl["mlp"]["wi"], "wg": bl["mlp"]["wg"],
          "wo2": bl["mlp"]["wo"]}
    table = f32(p["embed"]["tok"][:v])
    x, _ = jax.lax.scan(layer, table[tokens],
                        jax.tree_util.tree_map(f32, ws))
    x = norm(x[:, -1], f32(p["final_norm"]["scale"]))
    return dot("rd,vd->rv", x, table)


def _logits(a, config, q):
    """Last-position logits of every request, over prompt + token, in
    blocks of requests small enough to hold their attention scores."""
    tokens = jnp.concatenate([a["prompt"], a["token"][:, None]], axis=1)
    r, t = tokens.shape
    per_request = 4 * config["num_heads"] * t * t
    blk = max(1, min(r, REF_SCORE_BYTES // per_request))
    while r % blk:
        blk -= 1
    out = jax.lax.map(lambda tk: _forward_last(a["params"], tk, config, q),
                      tokens.reshape(r // blk, blk, t))
    return out.reshape(r, -1)


def reference(a, config):
    """Every array the entry returns, in float32 at 'highest'."""
    return {"logits": _logits(a, config, _identity)}


def control(a, config):
    """The reference with every matmul's operands rounded to float8_e4m3
    (4 exponent, 3 mantissa bits): the precision below bfloat16."""
    return {"logits": _logits(a, config, _fp8)}


def work(config, traffic):
    """Algorithmic FLOPs and minimum HBM bytes of one call: every
    request's token through every layer, with attention over ``context``
    cache rows; the weights read once, each request's K and V rows read
    (the new one written), the logits written in float32.
    ``attention`` is the part the decode kernel does."""
    n_l, d, h, kv, hd, f, v = _dims(config)
    r, t = traffic["requests"], traffic["context"]
    item = jnp.dtype(config["dtype"]).itemsize
    matmul = d * (h + 2 * kv) * hd + h * hd * d + 3 * d * f
    weights = n_l * (matmul + 2 * d) + v * d + d
    attn = {"flops": r * n_l * 4 * h * hd * t,
            "bytes": r * n_l * 2 * kv * hd * t * item}
    return {"total": {"flops": r * (2 * n_l * matmul + 2 * d * v)
                      + attn["flops"],
                      "bytes": weights * item + attn["bytes"] + r * v * 4},
            "attention": attn}
