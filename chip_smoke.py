#!/usr/bin/env python3
"""Bring-up smoke on a TPU v5e: the compiler's Pallas path and the smollm
server, through their normal entry points, at real sizes, in one process.

  python chip_smoke.py             # one chip
  python chip_smoke.py --chips 4   # the two multi-chip checks only

One chip: five compiler cases through ``pom.compile(..., target="pallas")``
and ``jitted()`` / ``batched(8)`` at the ``benchmarks/workloads.py`` default
sizes, each against a plain ``jax.numpy`` reference of the same equations
at ``"highest"`` precision; then smollm_360m at its published width served
through ``launch.serve.serve`` (prefill then decode through the KV cache,
the decode attention a compiled Pallas kernel), its logits against a
float32 ``forward`` over the same tokens.  ``--chips 4``: ``batched(8)``
sharded over the four chips against ``jitted()`` per lane, and smollm
served on a 4x1 data mesh against the same prompts served on one device.

Every line before the last reports one case; the last line is the JSON
result.  It exits non-zero, printing no result, when JAX finds no TPU v5e
or when any case fails.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
import traceback
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

GEMM_TILES = (256, 256, 512)
# tolerances on max|got - ref| / max|ref|, with their reasons
TOL_DOT = (1e-2, "f32 operands may take one bf16 MXU pass (unit roundoff "
                 "2^-9); a 4096-term dot then errs ~2^-8 of its scale")
TOL_F32_SUM = (1e-5, "f32 products summed on the VPU in another order than "
                     "the reference's dot; 4096 terms at unit roundoff "
                     "2^-24 err ~sqrt(4096)*2^-24 of their scale")
TOL_F32 = (1e-5, "the same f32 adds and multiplies in the same order; "
                 "fused multiply-adds may differ by a few ulp (2^-23)")
TOL_BF16 = (5e-2, "bf16 weights and activations (unit roundoff 2^-9) "
                  "rounded in each of 32 layers; on a v5e any change of "
                  "program (batch size, partitioning, f32 reference) "
                  "moved these logits by 1.3e-2 to 2.0e-2")
TOL_F32_MESH = (1e-3, "f32 weights and activations, matmuls at "
                      "'highest': two partitionings of one step differ by "
                      "summation order only (unit roundoff 2^-24), far "
                      "below what a wrong row, position or cache slice "
                      "gives")


def log(msg: str) -> None:
    print(msg, flush=True)


def rel_err(got, ref) -> float:
    import jax.numpy as jnp
    got, ref = jnp.asarray(got, jnp.float32), jnp.asarray(ref, jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(ref)), 1e-30)
    return float(jnp.max(jnp.abs(got - ref)) / scale)


def n_kernels(compiled) -> int:
    return compiled.as_text().count('custom_call_target="tpu_custom_call"')


# --------------------------------------------------------------------------
# references: the workloads' equations in plain jax.numpy
# --------------------------------------------------------------------------
def gemm_ref(a):
    return {"C": a["C"] + a["A"] @ a["B"]}


def jacobi2d_ref(a, steps):
    A, B = a["A"], a["B"]
    for _ in range(steps):
        B = B.at[1:-1, 1:-1].set(0.2 * (A[1:-1, 1:-1] + A[1:-1, :-2]
                                        + A[1:-1, 2:] + A[2:, 1:-1]
                                        + A[:-2, 1:-1]))
        A = A.at[1:-1, 1:-1].set(B[1:-1, 1:-1])
    return {"A": A, "B": B}


def gaussian_ref(a):
    x = a["img"]
    g = 0.0625 * (x[:-2, :-2] + 2.0 * x[:-2, 1:-1] + x[:-2, 2:]
                  + 2.0 * x[1:-1, :-2] + 4.0 * x[1:-1, 1:-1]
                  + 2.0 * x[1:-1, 2:] + x[2:, :-2] + 2.0 * x[2:, 1:-1]
                  + x[2:, 2:])
    return {"out": a["out"].at[1:-1, 1:-1].set(g)}


def random_inputs(fn, seed: int, batch=None):
    """Every placeholder drawn on the device from ``seed``; arrays the
    program writes start random too, so untouched cells are checked."""
    import jax
    import jax.numpy as jnp
    key = jax.random.key(seed)
    out = {}
    for i, ph in enumerate(sorted(fn.placeholders.values(),
                                  key=lambda p: p.name)):
        shape = ((batch,) if batch else ()) + tuple(ph.shape)
        out[ph.name] = jax.random.normal(jax.random.fold_in(key, i), shape,
                                         jnp.float32)
    return out


def tiled_gemm(n: int, tiles=GEMM_TILES):
    """gemm scheduled with the DSL into (ti, tj, tk) tiles whose intra-tile
    loops are unrolled: the contraction kernel's blocks."""
    from benchmarks import workloads
    ti, tj, tk = tiles
    f = workloads.gemm(n)
    s = f.stmt("s")
    s.tile("i", "j", ti, tj, "i0", "j0", "i1", "j1")
    s.split("k", tk, "k0", "k1")
    s.unroll("i1", ti).unroll("j1", tj).unroll("k1", tk)
    s.pipeline("k0", 1)
    return f


# --------------------------------------------------------------------------
# phases
# --------------------------------------------------------------------------
def compiler_case(name, build, ref_fn, tol, kind, seed=0, batch=None,
                  need_kernel=False, **compile_kw):
    """compile -> jitted()/batched(batch) -> one warm run -> compare."""
    import jax
    from repro.core.pipeline import compile as pcompile
    from repro.runtime import pallas_interpret

    t0 = time.perf_counter()
    f = build()
    prog = pcompile(f.fn, target="pallas", outputs=f.outputs, **compile_kw)
    run = prog.jitted() if batch is None else prog.batched(batch)
    args = random_inputs(prog.fn, seed, batch)
    out = jax.block_until_ready(run(args))
    setup_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    out = jax.block_until_ready(run(args))
    run_s = time.perf_counter() - t0
    kernels = n_kernels(run.lower(args).compile())
    ref_one = ref_fn if batch is None else jax.vmap(ref_fn)
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(ref_one)(args)
    err = max(rel_err(out[k], ref[k]) for k in ref)
    log(f"[compiler] {name}: max_rel_err={err:.3e} tol={tol[0]:.0e} "
        f"({tol[1]}); tpu_custom_calls={kernels}; setup_s={setup_s:.3f} "
        f"run_s={run_s:.6f} (one run on {kind})")
    if not err <= tol[0]:
        raise AssertionError(f"{name}: error {err:.3e} over {tol[0]:.0e}")
    if need_kernel and not pallas_interpret() and kernels < 1:
        raise AssertionError(f"{name}: no Pallas kernel in the program")


def compiler_phase(kind, n_gemm=4096, n_jacobi=1024, steps=10,
                   n_gauss=4096, batch=8, tiles=GEMM_TILES):
    from benchmarks import workloads
    yield ("gemm tiled", lambda: compiler_case(
        f"gemm {n_gemm} tiled {tiles}", lambda: tiled_gemm(n_gemm, tiles),
        gemm_ref, TOL_DOT, kind, need_kernel=True))
    yield ("gemm dse", lambda: compiler_case(
        f"gemm {n_gemm} dse=True", lambda: workloads.gemm(n_gemm),
        gemm_ref, TOL_F32_SUM, kind, dse=True))
    yield ("jacobi2d", lambda: compiler_case(
        f"jacobi2d {n_jacobi}x{steps}",
        lambda: workloads.jacobi2d(n_jacobi, steps),
        lambda a: jacobi2d_ref(a, steps), TOL_F32, kind))
    yield ("gaussian", lambda: compiler_case(
        f"gaussian {n_gauss}", lambda: workloads.gaussian(n_gauss),
        gaussian_ref, TOL_F32, kind))
    yield ("gaussian batched", lambda: compiler_case(
        f"gaussian {n_gauss} batched({batch})",
        lambda: workloads.gaussian(n_gauss), gaussian_ref, TOL_F32, kind,
        batch=batch))


def smollm(cfg_overrides=None):
    """smollm_360m at its published width, decode attention in Pallas."""
    from repro.configs.base import get_config
    cfg = get_config("smollm_360m")
    return dataclasses.replace(cfg, use_pallas=True, **(cfg_overrides or {}))


def smollm_requests(cfg, batch, prompt_len, seed):
    """Random weights and prompts from ``seed``."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.models import init_params
    params = jax.jit(lambda k: init_params(k, cfg))(jax.random.key(seed))
    prompts = jnp.asarray(np.random.default_rng(seed).integers(
        0, cfg.vocab_size, (batch, prompt_len)), jnp.int32)
    return params, prompts


def model_phase(kind, cfg=None, batch=4, prompt_len=128, gen=32, seed=0):
    import jax
    import jax.numpy as jnp
    from repro.launch.serve import serve
    from repro.models import forward
    from repro.runtime import pallas_interpret
    cfg = cfg or smollm()
    params, prompts = smollm_requests(cfg, batch, prompt_len, seed)
    res = serve(cfg, params, prompts, gen)
    kernels = n_kernels(res.compiled)
    seq = jnp.concatenate([prompts, res.tokens[:, :-1]], axis=1)
    cfg32 = dataclasses.replace(cfg, dtype="float32", param_dtype="float32",
                                use_pallas=False)
    p32 = jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), params)
    with jax.default_matmul_precision("highest"):
        ref, _ = jax.jit(lambda p, t: forward(p, cfg32, tokens=t))(p32, seq)
    v = cfg.vocab_size               # the padded vocab's logits are -1e30
    err = rel_err(res.logits[..., :v], ref[..., :v])
    agree = float(jnp.mean(jnp.argmax(res.logits, -1) == jnp.argmax(ref, -1)))
    log(f"[model] {cfg.name} L={cfg.num_layers} d={cfg.d_model} "
        f"vocab={cfg.vocab_size} {cfg.dtype} batch={batch} "
        f"prompt={prompt_len} gen={gen}: logits max_rel_err={err:.3e} "
        f"tol={TOL_BF16[0]:.0e} ({TOL_BF16[1]}); argmax agreement "
        f"{agree:.4f}; decode-step tpu_custom_calls={kernels}; "
        f"compile_s={res.setup_s:.3f} prefill_s={res.prefill_s:.3f} "
        f"decode_s={res.decode_s:.3f} (one run on {kind})")
    if not err <= TOL_BF16[0]:
        raise AssertionError(f"smollm logits error {err:.3e} over "
                             f"{TOL_BF16[0]:.0e}")
    if not pallas_interpret() and kernels < 1:
        raise AssertionError("smollm decode step runs no Pallas kernel")


def batched_across_chips(kind, n=4096, batch=8):
    """``batched(batch)`` sharded over every local device vs ``jitted()``
    on each lane."""
    import jax
    from benchmarks import workloads
    from repro.core.pipeline import compile as pcompile
    prog = pcompile(workloads.gaussian(n).fn, target="pallas")
    br = prog.batched(batch)
    args = random_inputs(prog.fn, 0, batch)
    t0 = time.perf_counter()
    out = jax.block_until_ready(br(args))
    setup_s = time.perf_counter() - t0
    run = prog.jitted()
    err = 0.0
    for i in range(batch):
        lane = run({k: v[i] for k, v in args.items()})
        err = max(err, max(rel_err(out[k][i], lane[k]) for k in lane))
    log(f"[4-chip] gaussian {n} batched({batch}) over {br.devices} "
        f"devices vs jitted() per lane: max_rel_err={err:.3e} "
        f"tol={TOL_F32[0]:.0e} ({TOL_F32[1]}); setup_s={setup_s:.3f} "
        f"(one run on {kind})")
    if br.devices != len(jax.devices()):
        raise AssertionError(f"batched ran on {br.devices} devices")
    if not err <= TOL_F32[0]:
        raise AssertionError(f"batched lanes differ by {err:.3e}")


def agreed_err(a, b, prompt_len) -> tuple:
    """(max relative logits error, rows compared, greedy steps agreed) of
    two serve runs, each a ``(logits, tokens)`` pair, over the rows fed
    the same tokens: up to the first greedy token that differs in any
    request."""
    import jax
    import numpy as np
    (la, ta), (lb, tb) = jax.device_get(a), jax.device_get(b)
    same = np.all(ta == tb, axis=0)
    first = int(np.argmin(same)) if not same.all() else len(same)
    rows = min(prompt_len + first, la.shape[1])
    return rel_err(la[:, :rows], lb[:, :rows]), rows, first


def served_across_chips(kind, cfg=None, batch=4, prompt_len=128, gen=32,
                        seed=0):
    """smollm on an Nx1 data mesh vs the same prompts on one device.

    In bf16 any change of program (batch size, partitioning) moves the
    32-layer stack's logits by about 2e-2, so the bf16 mesh run is held
    to that, and the batch-4 run on one device against each prompt served
    alone shows the size of such a change with no mesh at all.  The same
    weights in f32 with f32-accurate matmuls then hold the mesh tightly to
    one device."""
    import jax
    import jax.numpy as jnp
    from repro.launch.mesh import make_mesh
    from repro.launch.serve import serve
    cfg = cfg or smollm()
    ndev = len(jax.devices())
    mesh = make_mesh((ndev, 1), ("data", "model"))
    params, prompts = smollm_requests(cfg, batch, prompt_len, seed)
    cfg32 = dataclasses.replace(cfg, dtype="float32", param_dtype="float32")
    p32 = jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), params)
    v = cfg.vocab_size               # the padded vocab's logits are -1e30

    def run(c, p, rows, mesh=None):
        r = serve(c, p, rows, gen, mesh=mesh)
        return r, (r.logits[..., :v], r.tokens)

    many, many_l = run(cfg, params, prompts, mesh)
    one, one_l = run(cfg, params, prompts)
    alone = [run(cfg, params, prompts[i:i + 1])[1] for i in range(batch)]
    alone_l = tuple(jnp.concatenate(x) for x in zip(*alone))
    with jax.default_matmul_precision("highest"):
        many32_l = run(cfg32, p32, prompts, mesh)[1]
        one32_l = run(cfg32, p32, prompts)[1]
    checks = [(f"{cfg.dtype} mesh vs batch {batch} on one device",
               many_l, one_l, TOL_BF16),
              (f"{cfg.dtype} batch {batch} vs each prompt alone, both on "
               "one device", one_l, alone_l, TOL_BF16),
              (f"float32 mesh vs batch {batch} on one device",
               many32_l, one32_l, TOL_F32_MESH)]
    failed = []
    for what, a, b, tol in checks:
        err, rows, first = agreed_err(a, b, prompt_len)
        log(f"[4-chip] {cfg.name} {what}: logits max_rel_err={err:.3e} over "
            f"{rows} steps (greedy tokens agree for {first}/{gen}) "
            f"tol={tol[0]:.0e} ({tol[1]})")
        if not err <= tol[0]:
            failed.append(f"{what}: {err:.3e} over {tol[0]:.0e}")
    log(f"[4-chip] {cfg.name} on a {ndev}x1 data mesh: decode_s="
        f"{many.decode_s:.3f} vs {one.decode_s:.3f} on one device "
        f"(one run on {kind})")
    if failed:
        raise AssertionError("; ".join(failed))


def run_phases(phases) -> int:
    failed = 0
    for name, fn in phases:
        try:
            fn()
        except Exception:
            failed += 1
            log(f"[FAILED] {name}")
            traceback.print_exc()
    return failed


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    args = ap.parse_args()

    from repro.runtime import enable_compile_cache
    cache = enable_compile_cache()
    import jax
    devs = jax.devices()
    dev = devs[0]
    kind = dev.device_kind
    if dev.platform != "tpu" or not ("v5 lite" in kind.lower()
                                     or "v5e" in kind.lower()):
        print(f"chip_smoke: needs a TPU v5e, JAX found {dev.platform} "
              f"({kind})", file=sys.stderr)
        return 2
    if len(devs) != args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found {len(devs)} "
              f"devices", file=sys.stderr)
        return 2
    log(f"device: {dev.platform} {kind} x{len(devs)}; compile cache {cache}")

    if args.chips == 1:
        phases = list(compiler_phase(kind))
        phases.append(("smollm server", lambda: model_phase(kind)))
    else:
        phases = [("batched across chips", lambda: batched_across_chips(kind)),
                  ("smollm on a data mesh",
                   lambda: served_across_chips(kind))]
    t0 = time.perf_counter()
    failed = run_phases(phases)
    log(f"{len(phases) - failed}/{len(phases)} cases passed in "
        f"{time.perf_counter() - t0:.1f}s")
    if failed:
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": kind, "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
