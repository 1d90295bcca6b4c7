"""Serving driver: a one-pass prefill, then decode through the cache.

  PYTHONPATH=src python -m repro.launch.serve --arch granite_4_0_h_micro \\
      --batch 4 --prompt-len 32 --gen 32 [--mesh 4x1] [--reduced]

Serves the published width by default; ``--reduced`` serves the CPU-sized
config of the same family.  ``--mesh DATAxMODEL`` partitions the step over
DATA x MODEL devices by the logical-axis rules of ``distributed.partition``,
as the train launcher does: requests over DATA, heads, MLP and vocab over
MODEL.

``decode_step_fn``, ``prefill_fn`` and ``fill_cache`` are the pieces
``serve()`` runs; other programs (the chip benchmark's model programs) call
them to serve as ``serve()`` does.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import time
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, get_config, reduced
from repro.core import telemetry
from repro.distributed.partition import (cache_logical_axes,
                                         logical_to_sharding,
                                         param_logical_axes)
from repro.distributed.sharding import MeshContext, use_mesh
from repro.launch.mesh import make_mesh
import repro.models as models
from repro.models import init_cache, init_params
from repro.runtime import enable_compile_cache, pallas_interpret

# the decode kernel walks the cache in blocks of 128 rows
CACHE_BLOCK = 128


@dataclasses.dataclass
class Served:
    logits: jnp.ndarray     # (B, prompt_len + gen - 1, V) fp32: a row per
                            # prompt position, then one per decode step
    tokens: jnp.ndarray     # (B, gen) greedy generations
    setup_s: float          # decode-step compile
    prefill_s: float        # the prefill's compile and run
    decode_s: float
    compiled: Any           # the compiled decode step (``.as_text()``: HLO)


def step_shardings(cfg: ModelConfig, mc: MeshContext, params, cache,
                   batch: int):
    """NamedShardings of the decode step's arguments on ``mc``'s mesh:
    ``(params, cache, per-request vector)``.  ``params`` and ``cache`` may
    be arrays or shapes; a mesh axis that does not divide a dimension is
    dropped from it."""
    return (logical_to_sharding(param_logical_axes(cfg), mc, params),
            logical_to_sharding(cache_logical_axes(cfg), mc, cache),
            logical_to_sharding(("batch",), mc,
                                jax.ShapeDtypeStruct((batch,), jnp.int32)))


def decode_step_fn(cfg: ModelConfig):
    """The decode step ``serve()`` runs: ``(params, cache, token, pos) ->
    (logits (B, V), cache)``, jitted with the cache donated, so that the
    cache is updated in place."""
    return jax.jit(lambda p, c, t, q: models.decode_step(p, cfg, c, t, q),
                   donate_argnums=(1,))


def prefill_fn(cfg: ModelConfig, logits: bool = True):
    """The one-pass prefill ``serve()`` runs: ``(params, tokens, cache,
    start) -> (logits (B, S, V), cache)``, jitted with the cache donated.
    With ``logits=False`` the logits come back as None and the
    unembedding is not computed."""
    if logits:
        return jax.jit(lambda p, t, c, i: models.prefill(p, cfg, t, c, i),
                       donate_argnums=(2,))
    return jax.jit(
        lambda p, t, c, i: (None, models.prefill(p, cfg, t, c, i)[1]),
        donate_argnums=(2,))


def fill_cache(fn, params, prompts: jnp.ndarray, cache, rows: Optional[int] = None):
    """Prefill ``prompts`` (B, S) into ``cache`` through ``fn``
    (``prefill_fn``), ``rows`` requests a call (all of them by default).
    The prefill is compiled first; each call then runs under a
    ``model.prefill`` span (``requests``, ``tokens``) that closes once the
    call has finished.  Returns (the logits of each call, cache)."""
    b, s = prompts.shape
    rows = rows or b
    if b % rows:
        raise ValueError(f"{b} requests do not split into calls of {rows}")
    part = prompts if rows == b else prompts[:rows]
    run = fn.lower(params, part, cache, 0).compile()
    out = []
    for i in range(0, b, rows):
        part = prompts if rows == b else prompts[i:i + rows]
        with telemetry.span("model.prefill", "model", requests=rows,
                            tokens=rows * s):
            logits, cache = jax.block_until_ready(run(params, part, cache, i))
        out.append(logits)
    return out, cache


def serve(cfg: ModelConfig, params, prompts: jnp.ndarray, gen: int,
          mesh: Optional[Mesh] = None) -> Served:
    """Prefill of ``prompts`` (B, S) in one forward pass (``prefill``;
    a stack ``prefill`` does not cover is teacher-forced through the
    decode step instead), then ``gen`` greedy tokens, every step through
    the cache.

    With a ``mesh`` the step runs under ``use_mesh``: parameters, cache and
    requests are placed by ``step_shardings`` and GSPMD partitions the
    step (the Pallas kernels are ``shard_map``'d in the model)."""
    b, plen = prompts.shape
    max_seq = -(-(plen + gen) // CACHE_BLOCK) * CACHE_BLOCK
    cache = init_cache(cfg, b, max_seq)
    pos = jnp.zeros((b,), jnp.int32)
    step = decode_step_fn(cfg)

    with use_mesh(mesh) if mesh is not None else contextlib.nullcontext() \
            as mc:
        if mc is not None:
            param_sh, cache_sh, row_sh = step_shardings(cfg, mc, params,
                                                        cache, b)
            params = jax.device_put(params, param_sh)
            cache = jax.device_put(cache, cache_sh)
            pos = jax.device_put(pos, row_sh)
            prompts = jax.device_put(prompts, NamedSharding(
                mc.mesh, P(*row_sh.spec, None)))

        t0 = time.perf_counter()
        compiled = step.lower(params, cache, prompts[:, 0], pos).compile()
        setup_s = time.perf_counter() - t0

        t0 = time.perf_counter()
        if models.prefills(cfg):
            (logits,), cache = fill_cache(prefill_fn(cfg), params, prompts,
                                          cache)
            rows, last = [logits], logits[:, -1]
        else:
            rows = []
            for t in range(plen):
                last, cache = step(params, cache, prompts[:, t], pos + t)
                rows.append(last[:, None])
        jax.block_until_ready(last)
        prefill_s = time.perf_counter() - t0

        tok = jnp.argmax(last, axis=-1).astype(jnp.int32)
        out = [tok]
        t0 = time.perf_counter()
        for t in range(plen, plen + gen - 1):
            logits, cache = step(params, cache, tok, pos + t)
            rows.append(logits[:, None])
            tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            out.append(tok)
        jax.block_until_ready(tok)
        decode_s = time.perf_counter() - t0
    return Served(jnp.concatenate(rows, axis=1), jnp.stack(out, axis=1),
                  setup_s, prefill_s, decode_s, compiled)


def main():
    ap = argparse.ArgumentParser(
        description="Serve random prompts: a one-pass prefill, then greedy "
                    "decode through the cache, and print the times.")
    ap.add_argument("--arch", default="smollm_360m",
                    help="a registered config, e.g. granite_4_0_h_micro")
    ap.add_argument("--batch", type=int, default=4, help="requests")
    ap.add_argument("--prompt-len", type=int, default=32,
                    help="tokens of each prompt, prefilled in one call")
    ap.add_argument("--gen", type=int, default=32,
                    help="tokens generated per request")
    ap.add_argument("--mesh", default="1x1", help="DATAxMODEL, e.g. 4x1")
    ap.add_argument("--reduced", action="store_true",
                    help="serve the reduced config (CPU-sized)")
    args = ap.parse_args()

    enable_compile_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    # attention and the SSM scan run their Pallas kernels wherever they compile
    cfg = dataclasses.replace(cfg, use_pallas=not pallas_interpret())
    d, m = (int(x) for x in args.mesh.split("x"))
    mesh = make_mesh((d, m), ("data", "model"))
    b, plen, g = args.batch, args.prompt_len, args.gen

    rng = np.random.default_rng(0)
    prompts = jnp.asarray(rng.integers(0, cfg.vocab_size, (b, plen)), jnp.int32)
    params = jax.jit(lambda k: init_params(k, cfg))(jax.random.key(0))
    res = serve(cfg, params, prompts, g, mesh=mesh)

    gen = np.asarray(res.tokens)
    print(f"arch={cfg.name} batch={b} prompt={plen} gen={g} "
          f"device={jax.devices()[0].device_kind} x{mesh.size}")
    print(f"compile: {res.setup_s:.2f}s")
    print(f"prefill: {res.prefill_s:.2f}s with its compile "
          f"({b * plen / max(res.prefill_s, 1e-9):.0f} tok/s)")
    print(f"decode:  {res.decode_s:.2f}s "
          f"({b * (g - 1) / max(res.decode_s, 1e-9):.0f} tok/s)")
    print("sample generations (token ids):")
    for i in range(min(b, 2)):
        print(f"  [{i}]", gen[i, :16].tolist())


if __name__ == "__main__":
    main()
