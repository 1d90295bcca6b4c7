"""Granite-4.0-H-Micro decode through the model stack, for the
configurations that name ``"program": "granite_4_0_h_micro"``: a program
that builds its own entry (``build``).

The model (``granite_4_0_h_micro.json``, the published config): 40 layers
set by ``layer_types``, Mamba-2 mixers and NoPE GQA attention, each
followed by a SwiGLU MLP, with muP multipliers and tied embeddings. The
traffic names ``requests`` and ``context``.

* ``inputs`` draws the weights, in the layout ``repro.models`` keeps and
  the type they are served in, each request's prompt of ``context - 1``
  tokens and its next token.
* ``build`` fills the cache through ``launch/serve.py``'s one-pass
  prefill (``prefill_fn``, ``fill_cache``), ``PREFILL_ROWS`` requests a
  call. The entry runs ``repro.models.decode_step`` for every request at
  position ``context - 1`` and returns ``{"logits"}``. Each call does the
  same work as a server's step: the attention layers re-write the same
  K/V row of the cache in place (the cache is donated), and each Mamba
  layer reads the state the prefill left and writes its new state into a
  donated buffer. ``serve()``'s own step (``decode_step_fn``) donates the
  whole cache and so advances the state; here the state read must stay,
  so the step is the same ``decode_step`` jitted with that donation.
* ``reference`` is a full forward over prompt and token in plain
  ``jax.numpy`` at float32 ``"highest"``, one request at a time and in
  blocks of heads, and imports nothing of the program: Mamba-2 in the
  SSD's masked quadratic form (arXiv:2405.21060, the form equal to the
  recurrence), attention without position embedding. ``control`` is the
  same with every matmul's operands rounded to float8_e4m3.

Departures from the published model: none in the equations. The weights
are random (``assumed`` in the configuration). The SSD's decay exponent
is taken as ``cum[t] - cum[s]`` of a float32 cumulative sum, exact to
about ``|cum| * 2**-24``.
"""
import dataclasses
import sys
import time

import jax
import jax.numpy as jnp

import repro.models as models
from repro.configs.base import get_config
from repro.launch.serve import fill_cache, prefill_fn
from repro.models.mamba2 import CONV_W, GROUPS
from repro.runtime import pallas_interpret

HIGHEST = jax.lax.Precision.HIGHEST
# the decode kernel walks the cache in blocks of 128 rows
CACHE_BLOCK = 128
# requests a prefill call takes: four 4096-token prompts keep the prefill's
# activations near 2 GB beside the weights and the cache on a 16 GB chip
PREFILL_ROWS = 4
# heads a block of the reference's (T, T) quadratic forms holds
REF_HEADS = 8
# std of the drawn embedding table: Hugging Face's initializer_range
# default; with x12 the embedding is of the size of the x0.22 residual
# branches, so the layers, not each token's own row, make the logits
EMBED_STD = 0.02


def _dims(config):
    """The sizes the program and the reference read, by their own names."""
    d, nh, ph = (config["hidden_size"], config["mamba_n_heads"],
                 config["mamba_d_head"])
    din = config["mamba_expand"] * d
    g, n = config["mamba_n_groups"], config["mamba_d_state"]
    return dict(
        layers=config["num_hidden_layers"], d=d, h=config["num_attention_heads"],
        kv=config["num_key_value_heads"], hd=config["assumed"]["head_dim"],
        f=config["shared_intermediate_size"], v=config["vocab_size"],
        nh=nh, ph=ph, din=din, g=g, n=n, conv=config["mamba_d_conv"],
        conv_dim=din + 2 * g * n)


def _period(layer_types):
    """(period, repeats): the shortest prefix whose repeats make the
    pattern."""
    lt = tuple(layer_types)
    for p in range(1, len(lt) + 1):
        if len(lt) % p == 0 and lt == lt[:p] * (len(lt) // p):
            return lt[:p], len(lt) // p


def _model_config(config):
    """The model stack's config with the sizes, multipliers and type the
    configuration states."""
    z = _dims(config)
    fixed = {"attention_bias": False, "mamba_proj_bias": False,
             "num_local_experts": 0, "hidden_act": "silu",
             "normalization_function": "rmsnorm", "tie_word_embeddings": True,
             "position_embedding_type": "nope", "state_dtype": "float32",
             "mamba_conv_bias": True, "mamba_n_groups": GROUPS}
    for key, want in fixed.items():
        if config[key] != want:
            raise ValueError(f"{key} = {config[key]!r}: the model and the "
                             f"reference implement {want!r}")
    # mamba_d_head is the width the model derives, d_inner / heads
    if z["din"] != z["nh"] * z["ph"] or z["d"] != z["h"] * z["hd"]:
        raise ValueError("head sizes do not fill their widths")
    if z["conv"] != CONV_W:
        raise ValueError(f"mamba_d_conv {z['conv']}: the model's conv is "
                         f"{CONV_W} wide")
    return dataclasses.replace(
        get_config(config["arch"]), num_layers=z["layers"], d_model=z["d"],
        num_heads=z["h"], num_kv_heads=z["kv"], head_dim=z["hd"], d_ff=z["f"],
        vocab_size=z["v"], norm_eps=config["rms_norm_eps"],
        embedding_multiplier=config["embedding_multiplier"],
        residual_multiplier=config["residual_multiplier"],
        attention_multiplier=config["attention_multiplier"],
        logits_scaling=config["logits_scaling"],
        layer_types=tuple(config["layer_types"]), position_embedding="nope",
        tie_embeddings=True, ssm_state=z["n"], ssm_heads=z["nh"],
        ssm_expand=config["mamba_expand"], dtype=config["dtype"],
        param_dtype=config["dtype"], use_pallas=not pallas_interpret())


def inputs(key, config, traffic):
    """Weights (``repro.models`` layout, the configuration's dtype, Mamba-2's
    dt_bias, A_log and D in float32 as the model keeps them; the embedding
    table padded to a multiple of 2048 rows), ``prompt`` (requests,
    context - 1) and ``token`` (requests,)."""
    z = _dims(config)
    d, f = z["d"], z["f"]
    period, reps = _period(config["layer_types"])
    dt = jnp.dtype(config["dtype"])
    r, t = traffic["requests"], traffic["context"]
    ks = iter(jax.random.split(key, 4 + 16 * len(period)))
    # a query and a key of unit-rms rows through N(0, s^2) weights have
    # products that sum over head_dim to a spread of sqrt(hd) d s^2, so the
    # scores spread by attention_score_std at the configured scale
    qk_std = (config["assumed"]["attention_score_std"]
              / (config["attention_multiplier"] * z["hd"] ** 0.5 * d)) ** 0.5

    def normal(shape, std, dtype=dt):
        return (jax.random.normal(next(ks), shape, jnp.float32)
                * std).astype(dtype)

    def near_one(*shape, dtype=dt):
        return (1.0 + normal(shape, 0.1, jnp.float32)).astype(dtype)

    def uniform(shape, lo, hi):
        return jax.random.uniform(next(ks), shape, jnp.float32, lo, hi)

    blocks = {}
    for i, kind in enumerate(period):
        blk = {"ln1": {"scale": near_one(reps, d)},
               "ln2": {"scale": near_one(reps, d)},
               "mlp": {"wi": normal((reps, d, f), d ** -0.5),
                       "wg": normal((reps, d, f), d ** -0.5),
                       "wo": normal((reps, f, d), f ** -0.5)}}
        if kind == "attention":
            h, kv, hd = z["h"], z["kv"], z["hd"]
            blk["attn"] = {"wq": normal((reps, d, h * hd), qk_std),
                           "wk": normal((reps, d, kv * hd), qk_std),
                           "wv": normal((reps, d, kv * hd), d ** -0.5),
                           "wo": normal((reps, h * hd, d), (h * hd) ** -0.5)}
        else:
            nh, din, cd = z["nh"], z["din"], z["conv_dim"]
            dt0 = jnp.exp(uniform((reps, nh), jnp.log(1e-3), jnp.log(1e-1)))
            blk["mamba"] = {
                "w_in": normal((reps, d, din + cd + nh), d ** -0.5),
                "conv": normal((reps, z["conv"], cd), z["conv"] ** -0.5),
                "conv_b": normal((reps, cd), 0.1),
                "dt_bias": dt0 + jnp.log(-jnp.expm1(-dt0)),
                "a_log": jnp.log(uniform((reps, nh), 1.0, 16.0)),
                "d_skip": near_one(reps, nh, dtype=jnp.float32),
                "w_out": normal((reps, din, d), din ** -0.5),
                "norm": {"scale": near_one(reps, din)}}
        blocks[f"l{i}"] = blk
    v_pad = -(-z["v"] // 2048) * 2048
    params = {"embed": {"tok": normal((v_pad, d), EMBED_STD)},
              "final_norm": {"scale": near_one(d)},
              "blocks": blocks}
    return {"params": params,
            "prompt": jax.random.randint(next(ks), (r, t - 1), 0, z["v"],
                                         jnp.int32),
            "token": jax.random.randint(next(ks), (r,), 0, z["v"], jnp.int32)}


def build(config, traffic, sets):
    """The entry: one decode step of every request at position
    ``context - 1``, after the prefill has filled the cache from the
    prompts."""
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
    t0 = time.perf_counter()
    _, cache = fill_cache(
        prefill_fn(cfg, logits=False), a["params"], a["prompt"],
        models.init_cache(cfg, b, max_seq),
        rows=PREFILL_ROWS if b % PREFILL_ROWS == 0 else b)
    print(f"granite_4_0_h_micro: prefill of {b} x {plen} tokens "
          f"{time.perf_counter() - t0:.3f} s with its compile",
          file=sys.stderr, flush=True)
    kv = {n: c for n, c in cache.items() if "k" in c}
    state = {n: c for n, c in cache.items() if "k" not in c}
    held = {"rw": (kv, jax.tree_util.tree_map(jnp.zeros_like, state))}
    del cache

    def step(params, rw, state, token, pos):
        """decode_step over the K/V stacks and the prefill's state; the
        new state lands in the donated buffer beside the K/V."""
        logits, new = models.decode_step(params, cfg, {**rw[0], **state},
                                         token, pos)
        return logits, ({n: new[n] for n in rw[0]},
                        {n: new[n] for n in state})
    # keep_unused: the buffer the state is written to is never read, and
    # must still be handed in to be donated
    step = jax.jit(step, donate_argnums=(1,), keep_unused=True)
    at = jnp.full((b,), plen, jnp.int32)
    vocab = config["vocab_size"]

    def entry(arrays):
        logits, held["rw"] = step(arrays["params"], held["rw"], state,
                                  arrays["token"], at)
        return {"logits": logits if logits.shape[-1] == vocab
                else logits[:, :vocab]}
    return entry


def _identity(x):
    return x


def _fp8(x):
    return jax.lax.reduce_precision(x, exponent_bits=4, mantissa_bits=3)


def ssd(xs, dt, a_log, bm, cm, dot):
    """Mamba-2's state-space dual in its masked quadratic form:
    ``y_t = sum_{s <= t} exp(sum_{s < k <= t} dt_k A) (C_t . B_s) dt_s x_s``
    with ``A = -exp(a_log)``, for xs (r, T, H, P), dt (r, T, H), bm and cm
    (r, T, G, N), each group shared by H/G heads; a (T, T) form per head,
    REF_HEADS heads at a time. ``dot(eq, x, y)`` computes every product."""
    r, t, nh, _ = xs.shape
    g = bm.shape[2]
    causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    cum = jnp.cumsum(dt * -jnp.exp(a_log), axis=1)
    gram = dot("rtgn,rsgn->rgts", cm, bm)                        # C_t . B_s
    ys = []
    for h0 in range(0, nh, REF_HEADS):
        hs = jnp.arange(h0, min(h0 + REF_HEADS, nh))
        c = jnp.moveaxis(cum[:, :, hs], 1, 2)                     # (r, hb, T)
        decay = jnp.exp(jnp.where(causal, c[..., :, None] - c[..., None, :],
                                  -jnp.inf))
        ys.append(dot("rhts,rshp->rthp", decay * gram[:, hs // (nh // g)],
                      xs[:, :, hs] * dt[:, :, hs, None]))
    return jnp.concatenate(ys, axis=2)


def _forward_last(p, tokens, config, q):
    """Logits (r, vocab) at the last position of ``tokens`` (r, T), float32,
    every matmul's operands passed through ``q``."""
    z = _dims(config)
    period, _ = _period(config["layer_types"])
    eps, rm = config["rms_norm_eps"], config["residual_multiplier"]
    r, t = tokens.shape
    d, h, kv, hd, nh, ph = z["d"], z["h"], z["kv"], z["hd"], z["nh"], z["ph"]
    din, g, n, cw = z["din"], z["g"], z["n"], z["conv"]
    grp = h // kv
    f32 = lambda x: x.astype(jnp.float32)  # noqa: E731

    def dot(eq, x, y):
        return jnp.einsum(eq, q(x), q(y), precision=HIGHEST)

    def norm(x, s):
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * s

    causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]

    def attention(x, w):
        """GQA causal attention, no position embedding, scores scaled by
        attention_multiplier; KV heads (with their query groups) a block
        at a time."""
        qh = dot("rtd,de->rte", x, w["wq"]).reshape(r, t, kv, grp, hd)
        kh = dot("rtd,de->rte", x, w["wk"]).reshape(r, t, kv, hd)
        vh = dot("rtd,de->rte", x, w["wv"]).reshape(r, t, kv, hd)
        blk = max(1, REF_HEADS // grp)
        outs = []
        for k0 in range(0, kv, blk):
            ks = slice(k0, k0 + blk)
            s = dot("rqkgd,rskd->rkgqs", qh[:, :, ks], kh[:, :, ks]) \
                * config["attention_multiplier"]
            s = jnp.where(causal, s, -jnp.inf)
            outs.append(dot("rkgqs,rskd->rqkgd", jax.nn.softmax(s, -1),
                            vh[:, :, ks]))
        o = jnp.concatenate(outs, axis=2).reshape(r, t, h * hd)
        return dot("rte,ed->rtd", o, w["wo"])

    def mamba(x, w):
        """Mamba-2: in_proj, causal conv with bias over xBC, SSD in its
        masked quadratic form plus D x, gated RMSNorm, out_proj."""
        zxbcdt = dot("rtd,de->rte", x, w["w_in"])
        gate = zxbcdt[..., :din]
        xbc = zxbcdt[..., din:din + din + 2 * g * n]
        dt_raw = zxbcdt[..., din + din + 2 * g * n:]
        pad = jnp.pad(xbc, ((0, 0), (cw - 1, 0), (0, 0)))
        xbc = jax.nn.silu(sum(pad[:, i:i + t] * w["conv"][i]
                              for i in range(cw)) + w["conv_b"])
        xs = xbc[..., :din].reshape(r, t, nh, ph)
        bm = xbc[..., din:din + g * n].reshape(r, t, g, n)
        cm = xbc[..., din + g * n:].reshape(r, t, g, n)
        dt = jax.nn.softplus(dt_raw + w["dt_bias"])              # (r, t, nh)
        y = ssd(xs, dt, w["a_log"], bm, cm, dot)
        y = y + xs * w["d_skip"][:, None]
        u = (y.reshape(r, t, din) * jax.nn.silu(gate)).reshape(r, t, g, -1)
        u = norm(u, 1.0).reshape(r, t, din) * w["norm"]["scale"]
        return dot("rte,ed->rtd", u, w["w_out"])

    def repeat(x, ws):
        for i, kind in enumerate(period):
            w = jax.tree_util.tree_map(f32, ws[f"l{i}"])
            y = norm(x, w["ln1"]["scale"])
            y = attention(y, w["attn"]) if kind == "attention" \
                else mamba(y, w["mamba"])
            x = x + rm * y
            y = norm(x, w["ln2"]["scale"])
            m = jax.nn.silu(dot("rtd,df->rtf", y, w["mlp"]["wg"])) \
                * dot("rtd,df->rtf", y, w["mlp"]["wi"])
            x = x + rm * dot("rtf,fd->rtd", m, w["mlp"]["wo"])
        return x, None

    table = f32(p["embed"]["tok"][:z["v"]])
    x = table[tokens] * config["embedding_multiplier"]
    x, _ = jax.lax.scan(repeat, x, p["blocks"])
    x = norm(x[:, -1], f32(p["final_norm"]["scale"]))
    return dot("rd,vd->rv", x, table) / config["logits_scaling"]


def _logits(a, config, q):
    """Last-position logits of every request, over prompt + token, one
    request at a time."""
    tokens = jnp.concatenate([a["prompt"], a["token"][:, None]], axis=1)
    out = jax.lax.map(lambda tk: _forward_last(a["params"], tk[None], config, q),
                      tokens)
    return out.reshape(tokens.shape[0], -1)


def reference(a, config):
    """Every array the entry returns, in float32 at 'highest'."""
    return {"logits": _logits(a, config, _identity)}


def control(a, config):
    """The reference with every matmul's operands rounded to float8_e4m3
    (4 exponent, 3 mantissa bits): the precision below bfloat16."""
    return {"logits": _logits(a, config, _fp8)}


def work(config, traffic):
    """Algorithmic FLOPs and minimum HBM bytes of one call: every
    request's token through every layer; the weights read once, each
    attention layer's K and V rows over ``context`` read, each Mamba
    layer's state (h and the conv window over xBC, float32) read and
    written, the logits written in float32. ``attention`` is the part the
    decode kernel does, ``ssm`` the state recurrence."""
    z = _dims(config)
    r, t = traffic["requests"], traffic["context"]
    item = jnp.dtype(config["dtype"]).itemsize
    kinds = list(config["layer_types"])
    n_attn, n_mamba = kinds.count("attention"), kinds.count("mamba")
    d, f, v = z["d"], z["f"], z["v"]
    h, kv, hd, nh, ph, n = z["h"], z["kv"], z["hd"], z["nh"], z["ph"], z["n"]
    din, cd, cw = z["din"], z["conv_dim"], z["conv"]
    attn_mm = d * (h + 2 * kv) * hd + h * hd * d
    mamba_mm = d * (din + cd + nh) + din * d
    mlp_mm = 3 * d * f
    matmul = n_attn * attn_mm + n_mamba * mamba_mm + len(kinds) * mlp_mm
    # bf16: the matmuls, norms, conv and its bias; float32: dt_bias, A_log, D
    weights = (matmul + v * d + d + len(kinds) * 2 * d
               + n_mamba * (cw * cd + cd + din)) * item + n_mamba * 3 * nh * 4
    state = nh * n * ph + (cw - 1) * cd                   # float32 a layer
    attn = {"flops": r * n_attn * 4 * h * hd * t,
            "bytes": r * n_attn * 2 * kv * hd * t * item}
    ssm = {"flops": r * n_mamba * (5 * nh * n * ph + 2 * cw * cd),
           "bytes": r * n_mamba * 2 * state * 4}
    return {"total": {"flops": r * (2 * matmul + 2 * d * v)
                      + attn["flops"] + ssm["flops"],
                      "bytes": weights + attn["bytes"] + ssm["bytes"]
                      + r * v * 4},
            "attention": attn, "ssm": ssm}
