import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512")
"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this:
  1. builds the production mesh (16x16 single-pod / 2x16x16 multi-pod),
  2. builds the jitted step (train_step for train shapes, prefill for
     prefill shapes, serve_step for decode shapes) with full in/out
     shardings,
  3. ``.lower(**ShapeDtypeStructs).compile()`` -- no allocation,
  4. records memory_analysis(), cost_analysis(), and collective bytes
     parsed from the compiled HLO, into a JSON cell report.

Usage:
  python -m repro.launch.dryrun --arch smollm_360m --shape train_4k [--multi-pod]
  python -m repro.launch.dryrun --all [--jobs 4]     # orchestrate subprocesses
"""
import argparse
import json
import re
import subprocess
import sys
import time
from typing import Dict, List, Optional, Tuple

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "results", "dryrun")

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

_COLL_RE = re.compile(
    r"=\s+(?:\()?([a-z0-9]+)\[([0-9,]*)\][^=]*?"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)")
_TUPLE_COLL_RE = re.compile(
    r"=\s+\(([^)]*)\)\s+(all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute)")
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def collective_bytes(hlo_text: str) -> Dict[str, float]:
    """Sum result-shape bytes of every collective op in the compiled HLO."""
    out: Dict[str, float] = {}
    for m in _COLL_RE.finditer(hlo_text):
        dtype, dims, op = m.groups()
        out[op] = out.get(op, 0.0) + _shape_bytes(dtype, dims)
    for m in _TUPLE_COLL_RE.finditer(hlo_text):
        shapes, op = m.groups()
        for sm in _SHAPE_RE.finditer(shapes):
            out[op] = out.get(op, 0.0) + _shape_bytes(*sm.groups())
    return out


def _structural_period(cfg) -> int:
    if cfg.layer_types:
        from repro.models.model import pattern
        return len(pattern(cfg)[0])
    if cfg.family == "moe":
        return cfg.moe_every
    if cfg.family == "hybrid":
        return cfg.attn_every or 1
    if cfg.family == "ssm":
        return cfg.slstm_every or 1
    return 1


def _build_args(cfg, shape, pcfg, mc, long_ctx):
    """(jitted, args) for one cell under an active mesh context."""
    import jax
    import jax.numpy as jnp
    from repro.distributed import step as step_mod
    from repro.models import init_cache, init_params
    from repro.optim import adamw_init

    params_shapes = jax.eval_shape(
        lambda k: init_params(k, cfg),
        jax.ShapeDtypeStruct((), jax.random.key(0).dtype))
    if shape.kind == "train":
        jitted, _ = step_mod.make_train_step(cfg, pcfg, mc)
        opt_shapes = jax.eval_shape(
            lambda p: adamw_init(p, cfg.optim_state_dtype,
                                 cfg.optim_second_dtype), params_shapes)
        return jitted, (params_shapes, opt_shapes,
                        step_mod.input_specs(cfg, shape))
    if shape.kind == "prefill":
        jitted, _ = step_mod.make_prefill_step(cfg, pcfg, mc)
        return jitted, (params_shapes, step_mod.input_specs(cfg, shape))
    b = shape.global_batch
    jitted, _ = step_mod.make_decode_step(cfg, pcfg, mc, b, shape.seq_len,
                                          long_context=long_ctx)
    cache_shapes = jax.eval_shape(lambda: init_cache(cfg, b, shape.seq_len))
    return jitted, (params_shapes, cache_shapes,
                    jax.ShapeDtypeStruct((b,), jnp.int32),
                    jax.ShapeDtypeStruct((b,), jnp.int32))


def _extrapolate_costs(cfg, shape, pcfg, mc, long_ctx) -> Dict:
    """Exact per-device flops/bytes/collectives: lax.scan bodies are counted
    ONCE by cost_analysis, so lower UNROLLED stacks at depth P and 2P and
    extrapolate linearly over the structural period P."""
    import dataclasses
    P = _structural_period(cfg)
    vals = {}
    for mult in (1, 2):
        depth = {"layer_types": cfg.layer_types[:P] * mult} \
            if cfg.layer_types else {}
        cfg2 = dataclasses.replace(cfg, scan_layers=False,
                                   unroll_inner_scans=True,
                                   num_layers=P * mult, **depth)
        jitted, args = _build_args(cfg2, shape, pcfg, mc, long_ctx)
        compiled = jitted.lower(*args).compile()
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0]
        coll = collective_bytes(compiled.as_text())
        vals[mult] = {
            "flops": float(ca.get("flops", 0.0)),
            "bytes": float(ca.get("bytes accessed", 0.0)),
            "coll": coll,
        }
    n_periods = cfg.num_layers / P
    out = {}
    for key in ("flops", "bytes"):
        per = vals[2][key] - vals[1][key]
        out[key] = max(vals[1][key] + per * (n_periods - 1), vals[1][key])
    coll = {}
    ops = set(vals[1]["coll"]) | set(vals[2]["coll"])
    for op in ops:
        v1 = vals[1]["coll"].get(op, 0.0)
        v2 = vals[2]["coll"].get(op, 0.0)
        coll[op] = max(v1 + (v2 - v1) * (n_periods - 1), 0.0)
    out["collectives"] = coll
    out["period"] = P
    out["note"] = ("unrolled-depth extrapolation; +-3% on heterogeneous "
                   "stacks whose depth is not a period multiple")
    return out


def recost_cell(arch: str, shape_name: str, multi_pod: bool,
                path: str) -> Dict:
    """Refresh only the 'corrected' cost extrapolation of an existing cell
    report (keeps the expensive memory/compile results)."""
    import jax
    from repro.configs.base import SHAPES, ParallelConfig, get_config
    from repro.distributed.sharding import use_mesh, current
    from repro.launch.mesh import make_production_mesh

    with open(path) as f:
        report = json.load(f)
    if report.get("status") != "ok":
        return report
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    pcfg = ParallelConfig()
    mesh = make_production_mesh(multi_pod=multi_pod)
    long_ctx = shape_name == "long_500k"
    with use_mesh(mesh):
        mc = current()
        report["corrected"] = _extrapolate_costs(cfg, shape, pcfg, mc,
                                                 long_ctx)
    with open(path, "w") as f:
        json.dump(report, f, indent=2)
    return report


# --- SSPerf hillclimb variants: named (cfg overrides, sharding-rule overrides)
VARIANTS: Dict[str, Tuple[Dict, Dict]] = {
    "base": ({}, {}),
    # Megatron-style sequence parallelism on the residual stream: converts
    # TP all-reduce into reduce-scatter + all-gather halves
    "sp": ({}, {"seq": ("model",)}),
    # bf16 unembed matmul (f32 accumulate): halves logits bytes
    "bf16logits": ({"logits_dtype": "bfloat16"}, {}),
    # remat only dot outputs instead of full blocks: fewer recompute flops
    "dots": ({"remat": "dots"}, {}),
    # no remat at all (memory-for-flops trade)
    "noremat": ({"remat": "none"}, {}),
    # combinations
    "sp+bf16logits": ({"logits_dtype": "bfloat16"}, {"seq": ("model",)}),
    "sp+bf16logits+dots": ({"logits_dtype": "bfloat16", "remat": "dots"},
                           {"seq": ("model",)}),
    # larger attention chunks (fewer scan steps, bigger score blocks)
    "chunk2k": ({"attn_chunk": 2048}, {}),
    "bf16logits+chunk2k": ({"logits_dtype": "bfloat16", "attn_chunk": 2048}, {}),
}


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             skip_compile: bool = False, variant: str = "base") -> Dict:
    import dataclasses
    import jax
    import jax.numpy as jnp
    from repro.configs.base import SHAPES, ParallelConfig, get_config
    from repro.distributed import step as step_mod
    from repro.distributed.sharding import use_mesh
    from repro.launch.mesh import make_production_mesh
    from repro.models import init_cache, init_params

    t0 = time.time()
    cfg = get_config(arch)
    cfg_over, rules_over = VARIANTS[variant]
    if cfg_over:
        cfg = dataclasses.replace(cfg, **cfg_over)
    shape = SHAPES[shape_name]
    pcfg = ParallelConfig()
    mesh = make_production_mesh(multi_pod=multi_pod)
    report: Dict = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "variant": variant,
        "chips": mesh.size, "kind": shape.kind,
        "params": cfg.param_count(),
        "active_params": cfg.active_param_count(),
    }

    # applicability gate (assignment rules)
    if shape_name == "long_500k" and not cfg.sub_quadratic:
        report["status"] = "skipped"
        report["reason"] = ("pure full-attention arch: 500k decode is "
                            "quadratic-KV; skipped per assignment (DESIGN.md SS5)")
        return report

    long_ctx = shape_name == "long_500k"
    with use_mesh(mesh, rules=rules_over or None):
        from repro.distributed.sharding import current
        mc = current()
        if shape.kind == "train":
            jitted, (param_sh, opt_sh, batch_sh) = step_mod.make_train_step(
                cfg, pcfg, mc)
            params_shapes = jax.eval_shape(
                lambda k: init_params(k, cfg),
                jax.ShapeDtypeStruct((), jax.random.key(0).dtype))
            from repro.optim import adamw_init
            opt_shapes = jax.eval_shape(
                lambda p: adamw_init(p, cfg.optim_state_dtype,
                                     cfg.optim_second_dtype), params_shapes)
            batch = step_mod.input_specs(cfg, shape)
            args = (params_shapes, opt_shapes, batch)
        elif shape.kind == "prefill":
            jitted, (param_sh, batch_sh) = step_mod.make_prefill_step(
                cfg, pcfg, mc)
            params_shapes = jax.eval_shape(
                lambda k: init_params(k, cfg),
                jax.ShapeDtypeStruct((), jax.random.key(0).dtype))
            batch = step_mod.input_specs(cfg, shape)
            args = (params_shapes, batch)
        else:  # decode
            b = shape.global_batch
            jitted, (param_sh, cache_sh, tok_sh) = step_mod.make_decode_step(
                cfg, pcfg, mc, b, shape.seq_len, long_context=long_ctx)
            params_shapes = jax.eval_shape(
                lambda k: init_params(k, cfg),
                jax.ShapeDtypeStruct((), jax.random.key(0).dtype))
            cache_shapes = jax.eval_shape(
                lambda: init_cache(cfg, b, shape.seq_len))
            args = (params_shapes, cache_shapes,
                    jax.ShapeDtypeStruct((b,), jnp.int32),
                    jax.ShapeDtypeStruct((b,), jnp.int32))

        lowered = jitted.lower(*args)
        report["lower_s"] = round(time.time() - t0, 1)
        if skip_compile:
            report["status"] = "lowered"
            return report
        t1 = time.time()
        compiled = lowered.compile()
        report["compile_s"] = round(time.time() - t1, 1)

        # --- memory ---------------------------------------------------------
        try:
            ma = compiled.memory_analysis()
            report["memory"] = {
                k: int(getattr(ma, k)) for k in
                ("argument_size_in_bytes", "output_size_in_bytes",
                 "temp_size_in_bytes", "generated_code_size_in_bytes")
                if hasattr(ma, k)}
            per_dev = (report["memory"].get("argument_size_in_bytes", 0)
                       + report["memory"].get("temp_size_in_bytes", 0))
            report["bytes_per_device"] = per_dev
            report["fits_16gb"] = bool(per_dev <= 16 * 2 ** 30)
        except Exception as e:   # pragma: no cover
            report["memory_error"] = str(e)

        # --- flops ----------------------------------------------------------
        try:
            ca = compiled.cost_analysis()
            if isinstance(ca, (list, tuple)):
                ca = ca[0]
            report["cost"] = {k: float(v) for k, v in ca.items()
                              if isinstance(v, (int, float)) and (
                                  k in ("flops", "bytes accessed")
                                  or k.startswith("bytes accessed"))}
        except Exception as e:   # pragma: no cover
            report["cost_error"] = str(e)

        # --- collectives ------------------------------------------------------
        try:
            txt = compiled.as_text()
            report["collectives_scanbody"] = collective_bytes(txt)
            report["hlo_bytes"] = len(txt)
        except Exception as e:   # pragma: no cover
            report["collective_error"] = str(e)

        # --- corrected per-device roofline inputs -----------------------------
        try:
            report["corrected"] = _extrapolate_costs(cfg, shape, pcfg, mc,
                                                     long_ctx)
        except Exception as e:   # pragma: no cover
            report["corrected_error"] = str(e)

        # analytic model flops (global): 6*N_active*tokens (train includes
        # backward); decode: 2*N_active per token
        tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode"
                                       else 1)
        na = cfg.active_param_count()
        if shape.kind == "train":
            mf = 6.0 * na * tokens
        else:
            mf = 2.0 * na * tokens
        report["model_flops_global"] = mf
        report["model_flops_per_device"] = mf / mesh.size

    report["status"] = "ok"
    report["total_s"] = round(time.time() - t0, 1)
    return report


def cell_path(arch: str, shape: str, multi_pod: bool) -> str:
    os.makedirs(RESULTS_DIR, exist_ok=True)
    tag = "pod2" if multi_pod else "pod1"
    return os.path.join(RESULTS_DIR, f"{arch}__{shape}__{tag}.json")


def orchestrate(jobs: int, archs: List[str], shapes: List[str],
                meshes: List[bool], force: bool = False) -> int:
    """Run cells in parallel subprocesses (compiles are single-threaded-ish;
    parallelism amortizes)."""
    todo = []
    for a in archs:
        for s in shapes:
            for mp in meshes:
                p = cell_path(a, s, mp)
                if force or not os.path.exists(p):
                    todo.append((a, s, mp, p))
    print(f"dry-run: {len(todo)} cells to run, {jobs} parallel jobs")
    procs: List[Tuple[subprocess.Popen, Tuple]] = []
    failed = 0

    def launch(item):
        a, s, mp, p = item
        cmd = [sys.executable, "-m", "repro.launch.dryrun", "--arch", a,
               "--shape", s, "--out", p] + (["--multi-pod"] if mp else [])
        env = dict(os.environ)
        env.pop("XLA_FLAGS", None)
        return subprocess.Popen(cmd, env=env, stdout=subprocess.PIPE,
                                stderr=subprocess.STDOUT)

    queue = list(todo)
    while queue or procs:
        while queue and len(procs) < jobs:
            item = queue.pop(0)
            procs.append((launch(item), item))
        done = []
        for i, (pr, item) in enumerate(procs):
            if pr.poll() is not None:
                done.append(i)
                out = pr.stdout.read().decode(errors="replace")
                a, s, mp, p = item
                tag = f"{a} x {s} x {'pod2' if mp else 'pod1'}"
                if pr.returncode != 0:
                    failed += 1
                    print(f"[FAIL] {tag}\n{out[-2000:]}")
                else:
                    print(f"[ok]   {tag}")
        for i in reversed(done):
            procs.pop(i)
        time.sleep(1.0)
    return failed


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--jobs", type=int, default=4)
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--out")
    ap.add_argument("--skip-compile", action="store_true")
    ap.add_argument("--recost", action="store_true",
                    help="refresh only the cost extrapolation of existing "
                         "cell reports")
    ap.add_argument("--variant", default="base",
                    help=f"perf variant: {list(VARIANTS)}")
    args = ap.parse_args()

    if args.all:
        from repro.configs.base import ARCH_IDS, SHAPES
        rc = orchestrate(args.jobs, ARCH_IDS, list(SHAPES), [False, True],
                         args.force)
        sys.exit(1 if rc else 0)

    if args.recost:
        path = args.out or cell_path(args.arch, args.shape, args.multi_pod)
        report = recost_cell(args.arch, args.shape, args.multi_pod, path)
        print(json.dumps(report.get("corrected", {}), indent=2))
        sys.exit(0)

    report = run_cell(args.arch, args.shape, args.multi_pod,
                      args.skip_compile, variant=args.variant)
    out = json.dumps(report, indent=2)
    print(out)
    if args.out:
        with open(args.out, "w") as f:
            f.write(out)
    if report.get("status") not in ("ok", "skipped", "lowered"):
        sys.exit(1)


if __name__ == "__main__":
    main()
