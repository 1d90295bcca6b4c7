"""Pallas backend: lower a POM-scheduled statement to ``pl.pallas_call``.

This is the TPU-native rendition of the paper's pragma semantics
(DESIGN.md SS2):

  * non-unrolled loop dims  -> the Pallas **grid** (Mosaic pipelines grid
    steps with double-buffered VMEM windows == `#pragma HLS pipeline`),
  * fully-unrolled dims     -> **block** dimensions computed as one vector/
    MXU op inside the kernel (== `#pragma HLS unroll`),
  * array partitioning      -> **BlockSpec** index maps (HBM->VMEM tiling).

One statement shape lowers to a kernel: the *contraction*
``D(i..) = D(i..) + X(..) * Y(..)`` (GEMM / 2MM / 3MM / BICG / GESUMMV) ->
``dot_general`` per block + grid accumulation over the reduction grid dims.
Any other statement raises ``PallasLowerError``.  A compiled lowering also
raises for BlockSpecs the TPU cannot tile (``_tpu_block_problem``), so only
schedules tiled to the (8, 128) layout reach Mosaic.

``PallasProgram.jitted()`` / ``batched(B)`` trace the whole loop AST into
one XLA computation: a nest whose statement lowers as above runs its
kernel, every other nest is vectorized (unit-stride and mixed-radix
digit windows, such as a split loop's ``32*i_o + i_u``, as slices; the
rest as gathers and scatters; then reductions), or becomes a
``fori_loop``.  Whether Pallas runs compiled or interpreted is
``repro.runtime.pallas_interpret()``: interpreted iff the backend is the CPU.
"""
from __future__ import annotations

import functools
import math
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.runtime import pallas_interpret

from .affine import LinExpr
from .cost_model import TPU_V5E
from .ir import BinOp, Call, Const, Expr, Function, IterVal, Load, Placeholder, Statement
from .ir import loads_of
from . import faultinject, telemetry

# every XLA compile of the program's steps counts in ``xla.compiles``
telemetry.watch_xla()


class PallasLowerError(Exception):
    pass


@dataclass
class _ArraySpec:
    name: str
    shape: Tuple[int, ...]
    block: Tuple[int, ...]
    index_map_exprs: Tuple[LinExpr, ...]   # over grid dim names (block indices)


def _dim_extents(stmt: Statement) -> Dict[str, int]:
    return stmt.trip_counts()


def _classify_dims(stmt: Statement) -> Tuple[List[str], List[str]]:
    """(grid_dims, block_dims): block dims must be fully unrolled."""
    trips = _dim_extents(stmt)
    grid, block = [], []
    for d in stmt.dims:
        f = stmt.unrolls.get(d, 1)
        t = trips.get(d, 1)
        if f >= t and f > 1:
            block.append(d)
        elif f > 1:
            raise PallasLowerError(f"partial unroll of {d} unsupported")
        else:
            grid.append(d)
    return grid, block


def _lower_bounds(stmt: Statement) -> Dict[str, int]:
    out = {}
    s = stmt.domain
    for i, d in enumerate(s.dims):
        los, _ = s.bounds_of(d, s.dims[i + 1:])
        const = [b for b in los if b.expr.is_const()]
        if not const:
            raise PallasLowerError(f"non-constant lower bound on {d}")
        from .affine import ceil_div
        out[d] = max(ceil_div(b.expr.const, b.div) for b in const)
    return out


def _array_spec(stmt: Statement, arr: Placeholder, idx: Sequence[LinExpr],
                grid: List[str], block: List[str],
                trips: Dict[str, int], lbs: Dict[str, int]) -> _ArraySpec:
    """Derive BlockSpec block shape + index_map from an affine access."""
    blk: List[int] = []
    imap: List[LinExpr] = []
    for p, e in enumerate(idx):
        # block extent along this array dim = span of e over block dims
        span = 1
        for d in block:
            c = e.coeff(d)
            if c != 0:
                span += abs(c) * (trips[d] - 1)
        # index map: e with block dims at their lower bound, grid dims as
        # block indices -- each grid-dim coefficient must be a multiple of
        # the block extent for a tile-aligned access
        base = LinExpr.cst(e.const)
        for d, c in e.coeffs.items():
            if d in block:
                base = base + LinExpr.cst(c * lbs.get(d, 0))
            else:
                base = base + LinExpr.var(d) * c
        for d in grid:
            c = base.coeff(d)
            if c % span != 0:
                raise PallasLowerError(
                    f"{arr.name} dim {p}: grid stride {c} not aligned to block {span}")
        if base.const % span != 0:
            raise PallasLowerError(f"{arr.name} dim {p}: offset not tile-aligned")
        imap.append(LinExpr({d: c // span for d, c in base.coeffs.items()},
                            base.const // span))
        blk.append(span)
    return _ArraySpec(arr.name, arr.shape, tuple(blk), tuple(imap))


def _match_contraction(stmt: Statement) -> Optional[Tuple[Load, Load, Load]]:
    """D = D + X*Y  (accumulation contraction). Returns (acc, X, Y)."""
    b = stmt.body
    if not (isinstance(b, BinOp) and b.op == "+"):
        return None
    sides = [(b.lhs, b.rhs), (b.rhs, b.lhs)]
    for acc, mulexpr in sides:
        if (isinstance(acc, Load) and acc.array.name == stmt.store.array.name
                and isinstance(mulexpr, BinOp) and mulexpr.op == "*"
                and isinstance(mulexpr.lhs, Load) and isinstance(mulexpr.rhs, Load)):
            if all((a - b_).key() == ((), 0) for a, b_ in zip(acc.idx, stmt.store.idx)):
                return acc, mulexpr.lhs, mulexpr.rhs
    return None


def _tpu_block_problem(spec: _ArraySpec) -> Optional[str]:
    """Why Mosaic would refuse ``spec``'s block, or None.  The last two
    block dims must be multiples of (8, 128) or equal the array's; a
    rank-1 block must be the whole array or a multiple of 128."""
    blk, shp = spec.block, spec.shape
    if len(blk) == 1:
        if blk[0] != shp[0] and blk[0] % 128:
            return (f"{spec.name}: rank-1 block {blk} of {shp} is neither "
                    f"the whole array nor a multiple of 128")
        return None
    for b, s, m in zip(blk[-2:], shp[-2:], (8, 128)):
        if b != s and b % m:
            return (f"{spec.name}: block {blk} of {shp} breaks the TPU "
                    f"tiling (last two dims multiples of (8, 128) or equal "
                    f"to the array's)")
    return None


# (schedule signature, array shapes/dtypes, mode) -> runner; ``mode`` is
# "interpret" or "compiled"
_PALLAS_RUNNER_CACHE: Dict[Tuple, Callable] = {}
_PALLAS_RUNNER_CACHE_MAX = 1024

# backward-compat alias (caching.clear_all reaches in by the old name)
_LOWER_CACHE = _PALLAS_RUNNER_CACHE


def lower_stmt_pallas(stmt: Statement, interpret: Optional[bool] = None) -> Callable:
    """Compile one scheduled statement into a jit'd pallas_call wrapper.

    Returns ``f(arrays: dict[str, jnp.ndarray]) -> jnp.ndarray`` producing the
    updated destination array.

    Lowerings are memoized on (statement schedule signature, array
    shapes/dtypes, requested mode), and the returned runner builds its
    ``pl.pallas_call`` once per observed output shape/dtype — repeated
    ``run()`` calls reuse the compiled callable instead of rebuilding it.
    ``interpret=None`` defers to ``pallas_interpret()``.
    """
    if interpret is None:
        interpret = pallas_interpret()
    from . import caching
    key = None
    if caching.ENABLED:
        arrays_sig = tuple((a.name, a.shape, a.dtype.name) for a in
                           [stmt.store.array] + [ld.array
                                                 for ld in loads_of(stmt.body)])
        key = (stmt.schedule_signature(), arrays_sig,
               "interpret" if interpret else "compiled")
        hit = _PALLAS_RUNNER_CACHE.get(key)
        if hit is not None:
            return hit
    # span covers only the actual lowering work; memoized hits return above
    with telemetry.span("backend.lower", _cat="backend", backend="pallas",
                        statement=stmt.name, interpret=interpret):
        run = _lower_stmt_pallas_compute(stmt, interpret)
    if key is not None:
        if len(_PALLAS_RUNNER_CACHE) >= _PALLAS_RUNNER_CACHE_MAX:
            _PALLAS_RUNNER_CACHE.clear()
        _PALLAS_RUNNER_CACHE[key] = run
    return run


def _lower_stmt_pallas_compute(stmt: Statement, interpret: bool,
                               pure: bool = False) -> Callable:
    grid_dims, block_dims = _classify_dims(stmt)
    trips = _dim_extents(stmt)
    lbs = _lower_bounds(stmt)
    for d in grid_dims:
        if lbs[d] != 0:
            raise PallasLowerError(f"grid dim {d} must start at 0")

    store_arr, store_idx = stmt.store_access()
    contraction = _match_contraction_composed(stmt)
    if contraction is None:
        raise PallasLowerError("statement is not a supported contraction; "
                               "use the JAX oracle or a dedicated kernel")
    (x_arr, x_idx), (y_arr, y_idx) = contraction

    specs: Dict[str, _ArraySpec] = {}
    order: List[Tuple[str, Tuple[LinExpr, ...]]] = []
    for arr, idx in [(x_arr, x_idx), (y_arr, y_idx), (store_arr, store_idx)]:
        specs[arr.name] = _array_spec(stmt, arr, idx, grid_dims, block_dims,
                                      trips, lbs)
        order.append((arr.name, idx))
    if not interpret:
        for spec in specs.values():
            problem = _tpu_block_problem(spec)
            if problem:
                raise PallasLowerError(f"{stmt.name}: {problem}")
        # x, y, init and out blocks, each double-buffered by the pipeline
        itemsize = {a.name: jnp.dtype(a.dtype.np or jnp.bfloat16).itemsize
                    for a in (x_arr, y_arr, store_arr)}
        vmem = 2 * sum(math.prod(specs[n].block) * itemsize[n]
                       for n in (x_arr.name, y_arr.name,
                                 store_arr.name, store_arr.name))
        if vmem > TPU_V5E.vmem_bytes:
            raise PallasLowerError(
                f"{stmt.name}: blocks need {vmem} bytes of VMEM double-"
                f"buffered, over the {TPU_V5E.vmem_bytes} available")

    out_spec = specs[store_arr.name]
    # reduction grid dims: grid dims that do not appear in the store index map
    used = set()
    for e in out_spec.index_map_exprs:
        used |= set(e.vars())
    red_dims = [d for d in grid_dims if d not in used]

    # contraction block dims: shared between x and y but not in store
    store_block_vars = set()
    for e in store_idx:
        store_block_vars |= {d for d in e.vars() if d in block_dims}
    x_vars = set(v for e in x_idx for v in e.vars() if v in block_dims)
    y_vars = set(v for e in y_idx for v in e.vars() if v in block_dims)
    k_vars = (x_vars & y_vars) - store_block_vars

    def idx_fn(exprs: Tuple[LinExpr, ...]):
        def f(*gids):
            env = dict(zip(grid_dims, gids))
            return tuple(
                sum((env[d] * c for d, c in e.coeffs.items()), 0) + e.const
                for e in exprs)
        return f

    grid = tuple(trips[d] for d in grid_dims)

    def _axes(idx: Tuple[LinExpr, ...]) -> List[Optional[str]]:
        """block dim indexing each array axis (None when axis is not blocked)."""
        out = []
        for e in idx:
            bs = [d for d in e.vars() if d in block_dims]
            out.append(bs[0] if bs else None)
        return out

    x_axes, y_axes, o_axes = _axes(x_idx), _axes(y_idx), _axes(store_idx)

    def kernel(x_ref, y_ref, init_ref, o_ref):
        if red_dims:
            first = functools.reduce(
                lambda a, b: a & b,
                [pl.program_id(grid_dims.index(d)) == 0 for d in red_dims])

            @pl.when(first)
            def _init():
                o_ref[...] = init_ref[...]
        else:
            o_ref[...] = init_ref[...]

        xb = x_ref[...]
        yb = y_ref[...]
        # align axes: contract over k_vars, batch over store_block_vars
        k_list = sorted(k_vars)
        xc = [x_axes.index(k) for k in k_list if k in x_axes]
        yc = [y_axes.index(k) for k in k_list if k in y_axes]
        dn = (((tuple(xc), tuple(yc))), ((), ()))
        acc = jax.lax.dot_general(xb, yb, dn,
                                  preferred_element_type=jnp.float32)
        # dot_general output axes: x free axes then y free axes; map to out
        x_free = [a for i, a in enumerate(x_axes) if i not in xc]
        y_free = [a for i, a in enumerate(y_axes) if i not in yc]
        out_order = x_free + y_free
        perm = []
        for a in o_axes:
            if a in out_order:
                perm.append(out_order.index(a))
        if len(perm) == len(out_order) and perm != list(range(len(perm))):
            acc = jnp.transpose(acc, perm)
        acc = acc.reshape(o_ref.shape)
        o_ref[...] += acc.astype(o_ref.dtype)

    x_spec, y_spec = specs[x_arr.name], specs[y_arr.name]

    # one pallas_call per observed output shape/dtype; repeated run() calls
    # (the common case in autotuning sweeps) reuse the built callable
    call_cache: Dict[Tuple, Callable] = {}

    def _call_for(shape: Tuple[int, ...], dtype) -> Callable:
        ck = (shape, jnp.dtype(dtype).name)
        fn = call_cache.get(ck)
        if fn is None:
            fn = pl.pallas_call(
                kernel,
                grid=grid,
                in_specs=[
                    pl.BlockSpec(x_spec.block, idx_fn(x_spec.index_map_exprs)),
                    pl.BlockSpec(y_spec.block, idx_fn(y_spec.index_map_exprs)),
                    pl.BlockSpec(out_spec.block, idx_fn(out_spec.index_map_exprs)),
                ],
                out_specs=pl.BlockSpec(out_spec.block,
                                       idx_fn(out_spec.index_map_exprs)),
                out_shape=jax.ShapeDtypeStruct(shape, dtype),
                interpret=interpret,
                name=stmt.name,
            )
            call_cache[ck] = fn
        return fn

    def run_pure(arrays: Dict[str, jnp.ndarray]) -> jnp.ndarray:
        x = jnp.asarray(arrays[x_arr.name])
        y = jnp.asarray(arrays[y_arr.name])
        o = jnp.asarray(arrays[store_arr.name])
        return _call_for(o.shape, o.dtype)(x, y, o)

    if pure or interpret:
        # ``pure``: trace-friendly, for the jit-traced program
        return run_pure

    def run(arrays: Dict[str, jnp.ndarray]) -> jnp.ndarray:
        # a compiled kernel that fails raises, naming the statement
        try:
            if faultinject.fires("backend.lower"):
                raise RuntimeError("injected Mosaic lowering failure")
            return run_pure(arrays)
        except Exception as e:  # Mosaic/XLA raise backend-specific types
            raise PallasLowerError(
                f"{stmt.name}: compiled Pallas kernel failed: "
                f"{type(e).__name__}: {e}") from e

    return run


def _match_contraction_composed(stmt: Statement):
    """Contraction match on *composed* (current-dim) access functions."""
    m = _match_contraction(stmt)
    if m is None:
        return None
    _, xl, yl = m
    x_idx = tuple(stmt.subst_lin(e) for e in xl.idx)
    y_idx = tuple(stmt.subst_lin(e) for e in yl.idx)
    return (xl.array, x_idx), (yl.array, y_idx)


# ==========================================================================
# Compiled serving path: whole-program tracing, batching, scan-over-layers
# ==========================================================================
# The per-statement ``pallas_call`` wrappers above execute eagerly, one
# dispatch per statement per run.  The serving path instead *traces* the
# whole loop AST into one JAX computation (``_build_step``): vectorizable
# statement nests become gather/scatter + reductions, sequential loops
# become ``lax.fori_loop``, guards become ``lax.cond``, and ``ScanRegion``
# nodes (repeated isomorphic blocks, detected at the Graph IR level)
# compile one block body and ``lax.scan`` over the stacked per-block
# arrays.  The traced step is then jit'd for single-invocation serving and
# ``vmap``'d (+ ``shard_map`` across local devices) for batched serving.

from jax import lax


class TraceError(Exception):
    """The program cannot be traced into a single JAX computation, so it
    has no ``jitted()`` or ``batched()`` executor."""


_JNP_CALLS = {
    "exp": jnp.exp, "sqrt": jnp.sqrt, "abs": jnp.abs,
    "max": jnp.maximum, "min": jnp.minimum,
    "relu": lambda x: jnp.maximum(x, 0.0),
    "tanh": jnp.tanh,
}


def _lin_val(e: LinExpr, env: Dict):
    """Evaluate a LinExpr over an env of ints / traced scalars / grid
    arrays (broadcasting makes the mixed cases just work)."""
    v = e.const
    for k, c in e.coeffs.items():
        if c:
            v = v + env[k] * c
    return v


def _tdiv(a, d: int, is_lower: bool):
    """ceil_div (lower bounds) / floor_div (upper bounds) over ints or
    traced scalars — ``//`` matches python floor semantics in jnp."""
    if d == 1:
        return a
    return -((-a) // d) if is_lower else a // d


def _bound_val(lb, env: Dict):
    vals = [_tdiv(_lin_val(b.expr, env), b.div, lb.is_lower)
            for b in lb.bounds]
    if len(vals) == 1:
        return vals[0]
    acc = vals[0]
    for v in vals[1:]:
        acc = (jnp.maximum(acc, v) if lb.is_lower else jnp.minimum(acc, v)) \
            if not (isinstance(acc, int) and isinstance(v, int)) \
            else (max(acc, v) if lb.is_lower else min(acc, v))
    return acc


def _stmt_accesses(sn) -> Tuple:
    """(store_arr, store_idx, load_idx_by_id) with every index expression
    composed through ``iter_subst`` and renamed into loop-var space."""
    s = sn.stmt
    ren = sn.dim_map
    arr, sidx = s.store_access()
    store_idx = tuple(e.rename(ren) for e in sidx)
    by_id = {}
    for ld, (a, idx) in zip(loads_of(s.body), s.load_accesses()):
        by_id[id(ld)] = (a, tuple(e.rename(ren) for e in idx))
    return arr, store_idx, by_id


def _eval_body(sn, env: Dict, bufs: Dict, by_id: Dict,
               expr: Optional[Expr] = None,
               loaded: Optional[Dict[int, Any]] = None):
    """Evaluate the statement body (or its sub-expression ``expr``) over an
    env of scalars or grid arrays; a load whose id is in ``loaded`` takes
    that value instead of indexing its array."""
    s = sn.stmt
    ren = sn.dim_map
    loaded = loaded or {}

    def ev(e: Expr):
        if isinstance(e, Const):
            return e.value
        if isinstance(e, IterVal):
            return _lin_val(s.subst_lin(e.expr).rename(ren), env)
        if isinstance(e, Load):
            if id(e) in loaded:
                return loaded[id(e)]
            _, idx = by_id[id(e)]
            return bufs[e.array.name][tuple(_lin_val(x, env) for x in idx)]
        if isinstance(e, BinOp):
            a, b = ev(e.lhs), ev(e.rhs)
            if e.op == "+":
                return a + b
            if e.op == "-":
                return a - b
            if e.op == "*":
                return a * b
            if e.op == "/":
                return a / b
            raise TraceError(f"unknown op {e.op}")
        if isinstance(e, Call):
            fn = _JNP_CALLS.get(e.fn)
            if fn is None:
                raise TraceError(f"unknown call {e.fn}")
            return fn(*[ev(a) for a in e.args])
        raise TraceError(f"unknown expr {e!r}")

    return ev(s.body if expr is None else expr)


def _box_const(lb, ranges: Dict[str, Tuple[int, int]]) -> Optional[int]:
    """The value of a loop bound when it is one constant over the box of
    enclosing ``ranges`` (e.g. a split loop's ``min(4095 - 32*i_o, 31)``
    for ``i_o`` in [0, 127] is 31), else None.  Each term is affine, so
    its extremes over the box lie at corners, and ceil/floor division
    keeps them there."""
    lows, highs = [], []
    for b in lb.bounds:
        if any(v not in ranges for v in b.expr.vars()):
            return None
        lo = hi = b.expr.const
        for v, c in b.expr.coeffs.items():
            a, z = ranges[v]
            lo += min(c * a, c * z)
            hi += max(c * a, c * z)
        lows.append(_tdiv(lo, b.div, lb.is_lower))
        highs.append(_tdiv(hi, b.div, lb.is_lower))
    agg = max if lb.is_lower else min
    lo, hi = agg(lows), agg(highs)
    return lo if lo == hi else None


def _vec_plan(node) -> Optional[Tuple]:
    """Whole-nest vectorization plan for a single-statement ForNode chain.

    Returns ``(chain, sn, kept, red, rest_body)`` when the remaining nest
    can be evaluated all-iterations-at-once: bounds constant over the box
    of enclosing ranges (``_box_const``), one straight StmtNode leaf, an
    injective store over the kept dims (each kept var in exactly one store
    position, alone with coefficient ±1 or as a mixed-radix digit of a
    split loop), and no load of the stored
    array except the accumulator pattern ``D = D + rest`` (reduction dims)
    or a same-index read (pure map).  ``None`` → execute sequentially.
    """
    from .loop_ir import ForNode, StmtNode
    chain: List[Tuple[str, int, int]] = []
    ranges: Dict[str, Tuple[int, int]] = {}
    n = node
    while isinstance(n, ForNode):
        lo, hi = _box_const(n.lo, ranges), _box_const(n.hi, ranges)
        if lo is None or hi is None:
            return None
        chain.append((n.var, lo, hi))
        ranges[n.var] = (lo, hi)
        if len(n.body) != 1:
            return None
        n = n.body[0]
    if not isinstance(n, StmtNode) or not chain:
        return None
    sn = n
    s = sn.stmt
    arr, store_idx, _ = _stmt_accesses(sn)
    kept: Dict[str, int] = {}          # var -> store position
    for p, e in enumerate(store_idx):
        vs = [v for v in e.vars() if v in ranges]
        if any(v in kept for v in vs):
            return None
        if len(vs) == 1 and abs(e.coeff(vs[0])) != 1:
            return None
        # several vars in one position (a split loop, i = 32*i_o + i_u)
        # must encode the position injectively, as mixed-radix digits
        span = 0
        for v in sorted(vs, key=lambda v: abs(e.coeff(v))):
            if len(vs) > 1 and abs(e.coeff(v)) <= span:
                return None
            span += abs(e.coeff(v)) * (ranges[v][1] - ranges[v][0])
        for v in vs:
            kept[v] = p
    red = [v for v, _, _ in chain if v not in kept]

    # loads of the stored array: allowed only at exactly the store index
    acc_load = None
    rest_body = s.body
    if red:
        b = s.body
        if not (isinstance(b, BinOp) and b.op == "+"):
            return None
        for acc, rest in ((b.lhs, b.rhs), (b.rhs, b.lhs)):
            if (isinstance(acc, Load) and acc.array.name == arr.name
                    and all((a - b_).key() == ((), 0)
                            for a, b_ in zip(acc.idx, s.store.idx))):
                acc_load, rest_body = acc, rest
                break
        if acc_load is None:
            return None
        if any(ld.array.name == arr.name for ld in loads_of(rest_body)):
            return None
    else:
        for ld in loads_of(s.body):
            if ld.array.name == arr.name:
                if not all((a - b_).key() == ((), 0)
                           for a, b_ in zip(ld.idx, s.store.idx)):
                    return None
    return chain, sn, kept, red, rest_body


def _digits(e: LinExpr, where: Dict) -> Optional[List[Tuple[int, int, int]]]:
    """The chain vars of one index position as mixed-radix digits of one
    contiguous range, ``[(chain axis, coefficient, extent), ...]`` most
    significant first (``[]`` for a point), else None.

    Sorted by |coefficient|, the smallest must be 1 and each next must be
    the product of the extents below it (``32*i_o + i_u`` with ``i_u`` in
    0..31), and every coefficient has the position's one sign."""
    vs = sorted((v for v in e.vars() if v in where),
                key=lambda v: abs(e.coeff(v)))
    digits: List[Tuple[int, int, int]] = []
    radix = 1
    for v in vs:
        c = e.coeff(v)
        ax, lo, hi = where[v]
        if abs(c) != radix or (digits and (c > 0) != (digits[0][1] > 0)):
            return None
        digits.append((ax, c, hi - lo + 1))
        radix *= hi - lo + 1
    return digits[::-1]


def _slice_access(idx: Tuple[LinExpr, ...], chain, shape: Tuple[int, ...],
                  env: Dict) -> Optional[Tuple[List, List[int], List]]:
    """Classify one access of a vectorized nest for the slice path.

    Each index position must be a *window* (chain vars that are the dense
    mixed-radix digits of one contiguous range, ``_digits``: one var with
    coefficient +1 or -1, or a split loop's ``32*i_o + i_u``; plus a
    constant and outer ``env`` terms) or a *point* (no chain var), and no
    chain var may index two positions.  Returns ``(starts, sizes, axes)``
    per position: the first array index the window covers, its extent,
    and its digits ``[(chain axis, coefficient, extent), ...]`` most
    significant first (``[]`` for a point).  Returns None, so the access
    keeps its index grids, for any other access or when a start known at
    trace time puts the window outside the array."""
    where = {v: (ax, lo, hi) for ax, (v, lo, hi) in enumerate(chain)}
    starts: List = []
    sizes: List[int] = []
    axes: List = []
    seen = set()
    for e, dim in zip(idx, shape):
        digits = _digits(e, where)
        if digits is None or any(d[0] in seen for d in digits):
            return None
        for ax, c, _ in digits:
            seen.add(ax)
            v, lo, hi = chain[ax]
            e = e.substitute(v, LinExpr.cst(lo if c > 0 else hi))
        axes.append(digits)
        sizes.append(math.prod(n for _, _, n in digits))
        start = _lin_val(e, env)
        if sizes[-1] > dim or (isinstance(start, int)
                               and not 0 <= start <= dim - sizes[-1]):
            return None
        starts.append(start)
    return starts, sizes, axes


def _split_window(acc) -> bool:
    """Whether a ``_slice_access`` window has a position of two or more
    digits."""
    return any(len(a) > 1 for a in acc[2])


def _window(buf, starts: List, sizes: List[int]):
    """The window of ``buf`` at ``starts``: ``lax.slice`` when every start
    is known at trace time, else ``lax.dynamic_slice``."""
    if all(isinstance(s, int) for s in starts):
        return lax.slice(buf, starts, [s + z for s, z in zip(starts, sizes)])
    return lax.dynamic_slice(buf, starts, sizes)


def _load_window(buf, acc, chain):
    """A slice access's values shaped like its index-grid gather: one axis
    per chain var in chain order, of size 1 where the access omits it.

    A position whose digits count down is reversed whole; reversing the
    range reverses each of its digits, since they share one sign."""
    starts, sizes, axes = acc
    w = _window(buf, starts, sizes)
    rev = [p for p, a in enumerate(axes) if a and a[0][1] < 0]
    if rev:
        w = lax.rev(w, rev)
    digits = [d for a in axes for d in a]
    used = [ax for ax, _, _ in digits]
    w = w.reshape([n for _, _, n in digits])
    order = sorted(range(len(used)), key=used.__getitem__)
    if order != list(range(len(used))):
        w = w.transpose(order)
    return w.reshape([hi - lo + 1 if ax in used else 1
                      for ax, (_, lo, hi) in enumerate(chain)])


def _store_window(val, acc):
    """``val`` over the kept chain axes (every one indexes a window
    position of the store) brought to the store window's array order:
    the inverse of ``_load_window``."""
    _, sizes, axes = acc
    perm = [ax for a in axes for ax, _, _ in a]
    if perm != sorted(perm):
        val = val.transpose(perm)
    win = [a for a in axes if a]
    val = val.reshape([math.prod(n for _, _, n in a) for a in win])
    rev = [k for k, a in enumerate(win) if a[0][1] < 0]
    if rev:
        val = lax.rev(val, rev)
    return val.reshape(sizes)


def _run_vectorized(plan, bufs: Dict, env: Dict) -> Dict:
    """Execute a ``_vec_plan`` nest as one broadcasted expression over the
    chain's axes, reduced over the reduction axes, then stored.

    An access whose every index position is a window or a point
    (``_slice_access``) is a rectangular window of its array: a load
    takes it with ``lax.slice`` / ``lax.dynamic_slice``, reshaped into the
    digits of a split position (``32*i_o + i_u`` → ``(128, 32)``) and
    transposed into chain order, and the store writes it back through the
    inverse with ``lax.dynamic_update_slice`` (an accumulator adds into
    the window first, which equals a scatter-add because ``_vec_plan``
    proves the store injective).  Every other access (a conv's ``y + r``,
    ``4*i + j`` with ``j`` in 0..1, a diagonal) indexes its array with
    broadcast iota grids, a gather, and every other store scatters.  Each
    access counts once per trace in ``vec.slice_access`` or
    ``vec.gather_access``, and a window with a position of two or more
    digits in ``vec.digit_window_access`` too."""
    chain, sn, kept, red, rest_body = plan
    arr, store_idx, by_id = _stmt_accesses(sn)
    shape = tuple(hi - lo + 1 for _, lo, hi in chain)
    nd = len(chain)
    grids = dict(env)
    for ax, (v, lo, hi) in enumerate(chain):
        g = lo + jnp.arange(hi - lo + 1)
        grids[v] = g.reshape((1,) * ax + (len(g),) + (1,) * (nd - 1 - ax))

    loads = loads_of(rest_body if red else sn.stmt.body)
    loaded = {}
    n_digit = 0
    for ld in loads:
        a, idx = by_id[id(ld)]
        acc = _slice_access(idx, chain, bufs[a.name].shape, env)
        if acc is not None:
            loaded[id(ld)] = _load_window(bufs[a.name], acc, chain)
            n_digit += _split_window(acc)
    n_slice = len(loaded)
    n_gather = len(loads) - n_slice

    kchain = [c for c in chain if c[0] in kept]
    buf = bufs[arr.name]
    sacc = _slice_access(store_idx, kchain, buf.shape, env)
    if sacc is None:
        n_gather += 1
        # store index arrays over the *kept* axes only
        kenv = dict(env)
        for ax, (v, lo, hi) in enumerate(kchain):
            g = lo + jnp.arange(hi - lo + 1)
            kenv[v] = g.reshape((1,) * ax + (len(g),)
                                + (1,) * (len(kchain) - 1 - ax))
        sidx = tuple(_lin_val(e, kenv) for e in store_idx)
    else:
        n_slice += 1
        n_digit += _split_window(sacc)
    telemetry.counter("vec.slice_access").inc(n_slice)
    telemetry.counter("vec.gather_access").inc(n_gather)
    telemetry.counter("vec.digit_window_access").inc(n_digit)

    bufs = dict(bufs)
    if red:
        # D = D + sum(rest) over the reduction axes
        val = _eval_body(sn, grids, bufs, by_id, rest_body, loaded)
        val = jnp.broadcast_to(val, shape)
        red_axes = tuple(ax for ax, (v, _, _) in enumerate(chain) if v in red)
        reduced = val.sum(axis=red_axes).astype(buf.dtype)
        if sacc is None:
            bufs[arr.name] = buf.at[sidx].add(reduced)
        else:
            starts, sizes, _ = sacc
            bufs[arr.name] = lax.dynamic_update_slice(
                buf, _window(buf, starts, sizes)
                + _store_window(reduced, sacc), starts)
    else:
        val = _eval_body(sn, grids, bufs, by_id, loaded=loaded)
        val = jnp.broadcast_to(val, shape).astype(buf.dtype)
        if sacc is None:
            bufs[arr.name] = buf.at[sidx].set(val)
        else:
            bufs[arr.name] = lax.dynamic_update_slice(
                buf, _store_window(val, sacc), sacc[0])
    return bufs


def _exec_stmt_scalar(sn, bufs: Dict, env: Dict) -> Dict:
    """One statement instance with every loop var bound to a scalar."""
    arr, store_idx, by_id = _stmt_accesses(sn)
    val = _eval_body(sn, env, bufs, by_id)
    idx = tuple(_lin_val(e, env) for e in store_idx)
    bufs = dict(bufs)
    bufs[arr.name] = bufs[arr.name].at[idx].set(val)
    return bufs


def _build_step(fn: Function, ast, interpret: bool):
    """Trace the loop AST into ``step(bufs) -> bufs`` (pure, jit-able).

    Statement nests are vectorized where legal (``_run_vectorized``:
    unit-stride and mixed-radix digit windows as slices and
    ``dynamic_update_slice``, any other access through index-grid gathers
    and scatters); compiled (``interpret=False``), a nest whose statement
    lowers to a contraction kernel runs that ``pallas_call`` instead.
    Raises ``TraceError`` (possibly only at trace time) when some
    construct has no JAX rendition.
    """
    from .loop_ir import (DataflowRegion, ForNode, IfNode, ProgramAST,
                          ScanRegion, StmtNode, TaskNode)

    def run_nodes(nodes, bufs, env):
        for n in nodes:
            bufs = run_node(n, bufs, env)
        return bufs

    def run_node(node, bufs, env):
        if isinstance(node, (ProgramAST, DataflowRegion, TaskNode)):
            return run_nodes(node.body, bufs, env)
        if isinstance(node, ScanRegion):
            return run_scan(node, bufs, env)
        if isinstance(node, ForNode):
            if not interpret:
                runner = _nest_pallas_runner(node, env)
                if runner is not None:
                    name, dest, run = runner
                    bufs = dict(bufs)
                    with jax.named_scope(name):
                        bufs[dest] = run(bufs)
                    return bufs
            plan = _vec_plan(node)
            if plan is not None:
                _, sn, *_ = plan
                with jax.named_scope(sn.stmt.name):
                    return _run_vectorized(plan, bufs, env)
            lo = _bound_val(node.lo, env)
            hi = _bound_val(node.hi, env)

            def body(v, b):
                return run_nodes(node.body, b, {**env, node.var: v})

            return lax.fori_loop(lo, hi + 1, body, bufs)
        if isinstance(node, IfNode):
            preds = []
            static = True
            for c in node.conds:
                v = _lin_val(c.expr, env)
                p = (v == 0) if c.is_eq else (v >= 0)
                static = static and isinstance(p, (bool,))
                preds.append(p)
            if static:
                if all(preds):
                    return run_nodes(node.body, bufs, env)
                return bufs
            pred = functools.reduce(lambda a, b: a & b,
                                    [jnp.asarray(p) for p in preds])
            return lax.cond(pred,
                            lambda b: run_nodes(node.body, b, env),
                            lambda b: b, bufs)
        if isinstance(node, StmtNode):
            with jax.named_scope(node.stmt.name):
                return _exec_stmt_scalar(node, bufs, env)
        raise TraceError(f"unknown node {type(node).__name__}")

    def _nest_pallas_runner(node, env):
        """Compiled pallas_call for a single-statement nest at top level
        (no outer env) whose schedule the contraction lowering accepts —
        it refuses blocks the TPU cannot tile, and those nests vectorize."""
        if env:
            return None
        from .loop_ir import ForNode as _F, StmtNode as _S
        n = node
        while isinstance(n, _F):
            if len(n.body) != 1:
                return None
            n = n.body[0]
        if not isinstance(n, _S):
            return None
        s = n.stmt
        try:
            run = _lower_stmt_pallas_compute(s, interpret=False, pure=True)
        except PallasLowerError:
            return None
        arr, _ = s.store_access()
        return s.name, arr.name, run

    def run_scan(node, bufs, env):
        if env:  # a scan region nested under live loops: run unrolled
            return run_nodes(node.body, bufs, env)
        template = node.body[:node.template_len]
        xs = {tn: jnp.stack([bufs[c] for c in names])
              for tn, names in node.reads.items()}
        for tn, names in node.writes.items():
            # per-block initial contents of the written buffers (the
            # accumulation convs start from them)
            xs["\0init:" + tn] = jnp.stack([bufs[c] for c in names])
        carry0 = bufs[node.carry_in] if node.carry_in else jnp.zeros((1,))

        def body(carry, x):
            local = dict(bufs)
            if node.carry_in:
                local[node.carry_in] = carry
            for tn in node.reads:
                local[tn] = x[tn]
            for tn in node.writes:
                local[tn] = x["\0init:" + tn]
            local = run_nodes(template, local, {})
            outs = {tn: local[tn] for tn in node.writes}
            nc = local[node.carry_out] if node.carry_out else carry
            return nc, outs

        _, ys = lax.scan(body, carry0, xs)
        bufs = dict(bufs)
        for tn, names in node.writes.items():
            for b, cname in enumerate(names):
                bufs[cname] = ys[tn][b]
        return bufs

    def step(bufs: Dict[str, jnp.ndarray]) -> Dict[str, jnp.ndarray]:
        return run_node(ast, bufs, {})

    return step


class BatchedRunner:
    """``jit(vmap(step))`` over the whole program — one dispatch serves a
    batch of invocations.  With several local devices and a batch they
    divide, the vmapped step is ``shard_map``'d across them, one slice of
    the batch per device."""

    def __init__(self, program: "PallasProgram", batch_size: Optional[int],
                 step):
        self.program = program
        self.batch_size = batch_size
        batched = jax.vmap(step)
        self.devices = 1
        devs = jax.local_devices()
        if len(devs) > 1 and batch_size and batch_size % len(devs) == 0:
            from jax.sharding import Mesh, PartitionSpec as P
            import numpy as _np
            mesh = Mesh(_np.array(devs), ("batch",))
            batched = jax.shard_map(batched, mesh=mesh,
                                    in_specs=(P("batch"),),
                                    out_specs=P("batch"),
                                    check_vma=False)  # pallas_call outputs
            self.devices = len(devs)
        self._fn = jax.jit(batched)

    def _infer_batch(self, arrays: Dict[str, Any]) -> int:
        if arrays:
            return next(iter(arrays.values())).shape[0]
        if self.batch_size is None:
            raise ValueError(
                "cannot infer batch size: no input arrays were passed and "
                "the runner was built with batch_size=None")
        return self.batch_size

    def _bufs(self, arrays: Dict[str, Any]) -> Dict[str, Any]:
        b = self._infer_batch(arrays)
        if self.batch_size is not None and b != self.batch_size:
            raise ValueError(
                f"batched runner built for batch {self.batch_size}, "
                f"got {b}")
        return self.program._batch_bufs(arrays, b)

    def lower(self, arrays: Dict[str, Any]):
        """``jax.stages.Lowered`` of the batched step for ``arrays``."""
        return self._fn.lower(self._bufs(arrays))

    def __call__(self, arrays: Dict[str, Any]) -> Dict[str, Any]:
        with telemetry.span("backend.execute", _cat="backend",
                            backend="pallas_batched",
                            fn=self.program.fn.name) as sp:
            with telemetry.span("backend.bufs", _cat="backend"):
                bufs = self._bufs(arrays)
            if sp:
                sp.add(batch=next(iter(bufs.values())).shape[0])
            return self._fn(bufs)


class PallasProgram:
    """The ``compile(fn, target="pallas")`` artifact.

    Calling it runs ``jitted()`` when Pallas is compiled, and the legacy
    exact path (per-statement ``pallas_call`` plan, oracle fallback) when
    it is interpreted.  The serving surface:

    * ``jitted()``  — the whole program traced + jit'd as one XLA
      computation (vectorized nests, ``fori_loop`` sequential loops,
      ``lax.scan`` over detected ``ScanRegion`` blocks);
    * ``batched(B)`` — ``jit(vmap(step))`` (+ ``shard_map`` across local
      devices when they divide B), one dispatch per *batch* of
      invocations.

    Both raise ``TraceError`` for a program the tracer cannot express;
    neither falls back to the legacy or host path.
    """

    def __init__(self, fn: Function, ast, interpret: bool, legacy,
                 mode: str):
        self.fn = fn
        self.ast = ast
        self.interpret = interpret
        # "traced" (compiled: the traced step) | "pallas" (interpreted
        # per-stmt plan) | "oracle" (interpreted, host loop interpreter)
        self.mode = mode
        self._legacy = legacy
        self._step = None
        self._trace_error: Optional[Exception] = None
        self._jit = None
        self._batched: Dict[Optional[int], BatchedRunner] = {}

    def __call__(self, arrays: Dict[str, Any]) -> Dict[str, Any]:
        if self._legacy is None:
            return self.jitted()(arrays)
        return self._legacy(arrays)

    # -- traced serving path ------------------------------------------------
    def _dtype_of(self, ph) -> Any:
        return ph.dtype.np or jnp.bfloat16

    def _full_bufs(self, arrays: Dict[str, Any]) -> Dict[str, Any]:
        bufs = {}
        for ph in self.fn.placeholders.values():
            dt = self._dtype_of(ph)
            if ph.name in arrays:
                bufs[ph.name] = jnp.asarray(arrays[ph.name], dtype=dt)
            else:
                bufs[ph.name] = jnp.zeros(ph.shape, dtype=dt)
        return bufs

    def _batch_bufs(self, arrays: Dict[str, Any], b: int) -> Dict[str, Any]:
        bufs = {}
        for ph in self.fn.placeholders.values():
            dt = self._dtype_of(ph)
            if ph.name in arrays:
                v = jnp.asarray(arrays[ph.name], dtype=dt)
                if v.shape != (b,) + ph.shape:
                    raise ValueError(
                        f"{ph.name}: expected batched shape "
                        f"{(b,) + ph.shape}, got {v.shape}")
                bufs[ph.name] = v
            else:
                bufs[ph.name] = jnp.zeros((b,) + ph.shape, dtype=dt)
        return bufs

    def traceable(self) -> bool:
        """Whether the whole program traces into one JAX computation
        (checked once, via an abstract evaluation — no FLOPs spent)."""
        if self._step is None and self._trace_error is None:
            try:
                step = _build_step(self.fn, self.ast, self.interpret)
                spec = {ph.name: jax.ShapeDtypeStruct(ph.shape,
                                                      self._dtype_of(ph))
                        for ph in self.fn.placeholders.values()}
                jax.eval_shape(step, spec)
                self._step = step
            except Exception as e:
                self._trace_error = e
        return self._step is not None

    def _traced_step(self):
        if not self.traceable():
            raise TraceError(
                f"{self.fn.name}: cannot trace into one JAX computation: "
                f"{type(self._trace_error).__name__}: {self._trace_error}"
            ) from self._trace_error
        return self._step

    def jitted(self):
        """Single-invocation jit'd executor: ``run(arrays) -> dict``;
        ``run.lower(arrays)`` gives its ``jax.stages.Lowered``."""
        if self._jit is None:
            jfn = jax.jit(self._traced_step())

            def run(arrays: Dict[str, Any]) -> Dict[str, Any]:
                with telemetry.span("backend.execute", _cat="backend",
                                    backend="pallas_jit", fn=self.fn.name):
                    with telemetry.span("backend.bufs", _cat="backend"):
                        bufs = self._full_bufs(arrays)
                    return jfn(bufs)

            run.lower = lambda arrays: jfn.lower(self._full_bufs(arrays))
            self._jit = run
        return self._jit

    def batched(self, batch_size: Optional[int] = None) -> BatchedRunner:
        """Batched executor: every input carries a leading batch dim."""
        br = self._batched.get(batch_size)
        if br is None:
            br = BatchedRunner(self, batch_size, self._traced_step())
            self._batched[batch_size] = br
        return br
