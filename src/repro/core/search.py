"""Pluggable DSE search subsystem (paper §VI-B, generalized).

``dse.stage2`` used to be a single greedy ladder hard-wired into the DSE
engine.  This module factors the three concerns of bottleneck-oriented
search — **candidate generation** (``unroll_candidates`` /
``apply_parallel``), **candidate evaluation** (``SerialEvaluator``: each
candidate applied to the live function and costed in generation order),
and **candidate selection** (a ``SearchStrategy``) — into independent
pieces behind a strategy registry:

* ``greedy`` — the paper's ladder, re-expressed on the new interface and
  bit-identical (schedules, reports, action logs, *and* evaluation
  counters) to the pre-subsystem engine;
* ``beam``   — anchored beam search: keep the top-k parallelization
  states per rung.  The pure-greedy trajectory is pinned into the beam
  ("anchored"), so the final design is never worse than greedy's, while
  the other ``k-1`` slots explore runner-up candidates and early-exit
  branches.  Beams share the schedule-signature-keyed report caches of
  the incremental engine, so revisiting a design another beam already
  evaluated is a dictionary hit.  Each iteration with several live
  states runs as a **wave**: all rung preambles first, then per-state
  evaluation and decisions in state order — and states whose pending
  rung is identical (same base design, statement, and target
  parallelism: sibling branches of one rung always are) share a single
  evaluation (*dedup-and-credit*), so ``beam:8`` costs far less than 8
  greedy ladders.

Every evaluated design additionally lands in a :class:`ParetoArchive` of
``(latency, DSP, BRAM18, schedule signature)`` points with
dominated-point pruning, so a DSE run exports the latency/resource
*frontier* rather than a single winner (``auto_dse(..., archive=...)``;
``POM_DUMP_PARETO=<path>`` dumps it as JSON).

Strategies are selected by ``auto_dse(strategy="beam", beam_width=4)``,
by the ``POM_DSE_STRATEGY`` environment variable (``greedy`` /
``beam[:k][:latency|scalar]``), or by registering the matching stage-2
pass from ``pipeline.STAGE2_PASSES`` directly.
"""
from __future__ import annotations

import copy
import functools
import json
import os
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

from . import caching
from . import telemetry
from .cost_model import CostStats, DesignReport, HlsModel
from .depgraph import DepGraph, build_depgraph
from .ir import Function, Statement
from . import transforms as T


# --------------------------------------------------------------------------
# schedule snapshot / restore (search backtracking)
# --------------------------------------------------------------------------
def _snapshot(stmt: Statement):
    # the domain object is shared, not copied: BasicSet is immutable by
    # convention (every transform builds a fresh set), and sharing keeps
    # its memoized structural key alive across restore cycles
    return (stmt.domain, dict(stmt.iter_subst), dict(stmt.unrolls),
            stmt.pipeline_at, stmt.pipeline_ii, stmt.after_spec)


def _restore(stmt: Statement, snap) -> None:
    stmt.domain, subst, unrolls, pat, pii, after = snap
    stmt.iter_subst = dict(subst)
    stmt._subst_sig = None          # rebound in place: drop the memoized sig
    stmt.unrolls = dict(unrolls)
    stmt.pipeline_at, stmt.pipeline_ii, stmt.after_spec = pat, pii, after


def _snapshot_fn(fn: Function):
    return {s.uid: _snapshot(s) for s in fn.statements}, \
        {ph.name: dict(ph.partitions) for ph in fn.placeholders.values()}


def _restore_fn(fn: Function, snap) -> None:
    stmts, parts = snap
    for s in fn.statements:
        _restore(s, stmts[s.uid])
    for ph in fn.placeholders.values():
        ph.partitions = dict(parts[ph.name])


# --------------------------------------------------------------------------
# candidate generation
# --------------------------------------------------------------------------
@functools.lru_cache(maxsize=None)
def _unroll_candidates_cached(P: int) -> Tuple[Tuple[int, ...], ...]:
    out = [(P,)]
    f = 2
    while f * f <= P * 2 and f <= P:
        if P % f == 0:
            out.append((P // f, f))
        f *= 2
    if P > 1:
        out.append((P, 1))
    return tuple(out)


def unroll_candidates(P: int) -> List[Tuple[int, ...]]:
    """Factor splits of P over the two innermost dims (innermost-only,
    mixed, and outer-only — the outer-only shape parallelises independent
    recurrence chains, e.g. BICG's row dimension).  A pure function of
    ``P``, recomputed several times per rung (generation, dispatch,
    wave tallies) — memoized, returning a fresh list per call so callers
    may mutate their copy."""
    return list(_unroll_candidates_cached(P))


def apply_parallel(stmt: Statement, factors: Tuple[int, ...]) -> bool:
    """Split+unroll the innermost len(factors) dims by ``factors`` (outermost
    factor first), pipeline the level right above the unrolled loops, and
    cyclic-partition the touched arrays (paper Fig. 6)."""
    dims = list(stmt.dims)
    k = len(factors)
    if k > len(dims):
        return False
    trips = stmt.trip_counts()
    targets = dims[-k:]
    for d, f in zip(targets, factors):
        if f > trips.get(d, 1):
            return False
    # split each target dim and unroll the intra-tile loop; strip-mining
    # never reorders iterations (bijective, lex-order-preserving), so the
    # ladder skips the redundant legality check the user-facing DSL keeps
    new_inner: List[str] = []
    for d, f in zip(targets, factors):
        if f <= 1:
            continue
        d0, d1 = d + "_o", d + "_u"
        try:
            T.split(stmt, d, f, d0, d1, check=False)
        except T.IllegalTransform:
            return False
        new_inner.append(d1)
    # move all intra-tile loops innermost (keeping relative order)
    order = [x for x in stmt.dims if x not in new_inner] + new_inner
    try:
        old = stmt.domain
        T.permute_dims(stmt, order)
        if not T._legal(stmt):
            stmt.domain = old
            return False
    except Exception:
        return False
    for d1 in new_inner:
        stmt.unrolls[d1] = stmt.trip_counts().get(d1, 1)
    # pipeline right above the unrolled band
    outer_dims = [x for x in stmt.dims if x not in new_inner]
    if outer_dims:
        stmt.pipeline_at = outer_dims[-1]
        stmt.pipeline_ii = 1
    return True


# --------------------------------------------------------------------------
# transformed-node memo (cross-rung / cross-state candidate applies)
# --------------------------------------------------------------------------
# (uid, base schedule sig, factors) -> node snapshot with the candidate
# applied, or None when ``apply_parallel`` rejects the factors.  A rung
# always restores its node to the state-independent clean base recorded at
# first visit before splitting, so the transformed schedule is a pure
# function of this key: distinct beam states re-proposing the same
# (statement, P) rung — the common case on multi-statement workloads —
# restore the memoized schedule instead of re-running the split/permute/
# legality machinery (``SerialEvaluator.evaluate_factors``).  Cleared by
# ``caching.clear_all``.
_APPLY_CACHE: Dict[Tuple, Optional[tuple]] = {}
_APPLY_MISS = object()


def design_signature(fn: Function) -> Tuple:
    """Structural signature of the whole design (schedules + partitions +
    the effective dataflow toggle); the same shape the cost model keys its
    whole-design cache on.  The dataflow flag distinguishes the sequential
    and task-pipelined aggregations of one schedule in the Pareto archive
    (same loops, different latency/BRAM point)."""
    from .graph_ir import dataflow_effective
    return (tuple(s.schedule_signature() for s in fn.statements),
            tuple(sorted((ph.name, ph.part_sig())
                         for ph in fn.placeholders.values())),
            dataflow_effective(fn))


# --------------------------------------------------------------------------
# Pareto archive of evaluated designs
# --------------------------------------------------------------------------
@dataclass(frozen=True)
class DesignPoint:
    """One evaluated design: the archive's objective axes + identity."""
    latency: int
    dsp: int
    bram18: int
    signature: Tuple
    strategy: str
    feasible: bool
    # steady-state per-invocation II (DesignReport.ii_region): reported
    # metadata, NOT an objective axis — it is derived from the same
    # schedule the latency axis already ranks, so adding it would only
    # thin the frontier with duplicates of the latency ordering
    ii_region: int = 0

    def objectives(self) -> Tuple[int, int, int]:
        return (self.latency, self.dsp, self.bram18)

    def dominates(self, other: "DesignPoint") -> bool:
        a, b = self.objectives(), other.objectives()
        return all(x <= y for x, y in zip(a, b)) and a != b


class ParetoArchive:
    """Archive of every evaluated design with dominated-point pruning.

    Points minimize ``(latency, DSP, BRAM18)``.  ``frontier()`` is the
    non-dominated set among *feasible* designs; infeasible evaluations are
    counted but never archived as points.  ``add`` is deduplicated on the
    design's schedule signature, so cache-hit re-evaluations (stage-2
    backtracking restores previous designs constantly) cost one set
    lookup.
    """

    def __init__(self, keep_dominated: bool = False):
        self.points: List[DesignPoint] = []      # current non-dominated set
        self.dominated: List[DesignPoint] = []   # kept only on request
        self.keep_dominated = keep_dominated
        self.evaluated = 0                       # distinct designs seen
        self.infeasible = 0
        self._seen: set = set()

    def add(self, fn: Function, report: DesignReport,
            strategy: str = "?") -> Optional[DesignPoint]:
        """Record one evaluated design; returns the archived point (or None
        for duplicates / infeasible / dominated-on-arrival designs)."""
        sig = design_signature(fn)
        if sig in self._seen:
            return None
        self._seen.add(sig)
        self.evaluated += 1
        if not report.feasible:
            self.infeasible += 1
            return None
        dsp, bram18 = report.resource_vector
        pt = DesignPoint(report.latency, dsp, bram18,
                         sig, strategy, report.feasible,
                         getattr(report, "ii_region", 0))
        return self._insert(pt)

    def _insert(self, pt: DesignPoint) -> Optional[DesignPoint]:
        for p in self.points:
            if p.dominates(pt) or p.objectives() == pt.objectives():
                if self.keep_dominated:
                    self.dominated.append(pt)
                return None
        survivors, newly_dominated = [], []
        for p in self.points:
            (newly_dominated if pt.dominates(p) else survivors).append(p)
        if self.keep_dominated:
            self.dominated.extend(newly_dominated)
        survivors.append(pt)
        self.points = survivors
        return pt

    def frontier(self) -> List[DesignPoint]:
        """Non-dominated feasible designs, latency-ascending."""
        return sorted(self.points, key=lambda p: p.objectives())

    def best(self) -> Optional[DesignPoint]:
        front = self.frontier()
        return front[0] if front else None

    def __len__(self) -> int:
        return len(self.points)

    def to_json(self) -> Dict[str, Any]:
        return {
            "evaluated": self.evaluated,
            "infeasible": self.infeasible,
            "frontier": [
                {"latency": p.latency, "dsp": p.dsp, "bram18": p.bram18,
                 "ii_region": p.ii_region, "strategy": p.strategy}
                for p in self.frontier()
            ],
        }

    def dump(self, dest: str = "-") -> None:
        """Write the frontier as JSON to ``dest`` — the ``POM_DUMP_PARETO``
        hook.  ``-`` means stdout, ``stderr`` standard error, anything
        else a path; the stream cases flush explicitly
        (``telemetry.dump_stream``, shared with ``POM_TRACE=-``) so dumps
        interleave correctly with pytest capture and service logs."""
        telemetry.dump_stream(json.dumps(self.to_json(), indent=2), dest)


# --------------------------------------------------------------------------
# search context + ladder state
# --------------------------------------------------------------------------
@dataclass
class SearchContext:
    """Everything a strategy needs: the design under search, the evaluator
    model, the budget, and the (optional) Pareto archive."""
    fn: Function
    model: HlsModel
    max_parallel: int = 256
    archive: Optional[ParetoArchive] = None
    strategy_name: str = "greedy"
    g: Optional[DepGraph] = None
    by_uid: Dict[int, Statement] = field(default_factory=dict)

    def record(self, report: DesignReport) -> None:
        if self.archive is not None:
            self.archive.add(self.fn, report, self.strategy_name)

    def design_report(self) -> DesignReport:
        rep = self.model.design_report(self.fn)
        self.record(rep)
        return rep


@dataclass
class Candidate:
    """One evaluated parallelization candidate of a rung."""
    factors: Tuple[int, ...]
    report: DesignReport
    snap: tuple                       # node snapshot with candidate applied


@dataclass
class RungInfo:
    """What happened in one ladder rung (consumed by beam branching)."""
    uid: int
    P: int
    prev: tuple                       # node snapshot before the rung
    cands: List[Candidate]
    chosen: Optional[Candidate]       # accepted candidate (None = exit)
    sweep: Any = None                 # closed-form ii(unroll_vector), if any


@dataclass
class LadderState:
    """One point of the search: a full design plus the ladder's bookkeeping."""
    parallel_of: Dict[int, int]
    active: List[int]
    base_snaps: Dict[int, tuple]
    report: DesignReport
    actions: List[str]
    guard: int = 0
    lineage: bool = False             # on the pure-greedy trajectory
    snap: Any = None                  # _snapshot_fn when not live
    sig: Optional[Tuple] = None
    last_rung: Optional[RungInfo] = None

    def clone(self) -> "LadderState":
        return LadderState(dict(self.parallel_of), list(self.active),
                           dict(self.base_snaps), self.report,
                           list(self.actions), self.guard, False, self.snap,
                           self.sig, None)


def _refresh_partitions(fn: Function) -> None:
    from .dse import refresh_partitions
    refresh_partitions(fn)


def _restore_node(fn: Function, stmt: Statement, snap) -> None:
    _restore(stmt, snap)
    _refresh_partitions(fn)


def _init_ladder(ctx: SearchContext) -> LadderState:
    """Mirror of the pre-subsystem ``stage2`` preamble (order matters: the
    evaluation counters of the incremental engine must be bit-identical)."""
    fn = ctx.fn
    ctx.g = build_depgraph(fn)
    parallel_of = {s.uid: 1 for s in fn.statements}
    active = [s.uid for s in fn.statements]
    ctx.by_uid = {s.uid: s for s in fn.statements}
    # give every node a baseline pipeline (innermost) before the ladder
    for s in fn.statements:
        if s.pipeline_at is None and s.dims:
            s.pipeline_at = s.dims[-1]
            s.pipeline_ii = 1
    _refresh_partitions(fn)
    report = ctx.design_report()
    return LadderState(parallel_of, active, {}, report, [])


def _critical_bottleneck(ctx: SearchContext, st: LadderState) -> Optional[int]:
    paths = ctx.g.paths()
    if not paths:
        return None

    def path_lat(p):
        return sum(st.report.nodes[ctx.by_uid[u].name].latency for u in p)

    best = max(paths, key=path_lat)
    cands = [u for u in best if u in st.active]
    if not cands:
        cands = [u for u in st.active]
        if not cands:
            return None
    return max(cands, key=lambda u: st.report.nodes[ctx.by_uid[u].name].latency)


# --------------------------------------------------------------------------
# bound-and-confirm rung planning (POM_BOUND_PRUNE)
# --------------------------------------------------------------------------
# A rung's closed-form sweep yields an *admissible latency lower bound*
# per candidate (``HlsModel.latency_lower_bound``): the exact pipelined-
# node latency formula with the closed-form recurrence II substituted for
# the achieved II (achieved = max(recurrence, memory-port, ...) >= it).
# The evaluator uses it in two ways, both preserving bit-identity with
# exhaustive evaluation:
#
# * **static rule** (branching beams): confirm exactly the candidates
#   whose bound could still beat the rung's pre-evaluation bottleneck
#   latency (``bound is None or bound < cutoff``).  A pruned candidate
#   has node latency >= bound >= cutoff, so it can neither win
#   ``_rung_finish``'s strict-improvement accept nor pass ``_branches``'s
#   strict-improvement filter — the full candidate list minus provable
#   losers.
# * **two-round rule** (single-trajectory ladders, where only the argmin
#   matters): round 1 confirms every unbounded candidate plus the lowest-
#   bounded one; round 2 confirms only candidates whose bound could still
#   beat round 1's best confirmed node latency (generation-order tiebreak:
#   an equal bound survives only if it precedes the incumbent, since the
#   argmin's first-strict-improvement rule lets an earlier equal-latency
#   candidate win).
def _charge_pruned(model: HlsModel, dropped: int) -> None:
    if dropped:
        model.stats.pruned_candidates += dropped
        telemetry.REGISTRY.counter("dse.pruned_candidates").inc(dropped)


def _bound_plan(model: HlsModel, sweep,
                factor_list: Sequence[Tuple[int, ...]], cutoff: int
                ) -> Tuple[List[Optional[int]], List[int]]:
    """Per-candidate latency lower bounds + the static confirm frontier
    (generation-order indices).  Charges ``pruned_candidates`` for the
    statically excluded ones."""
    bounds = [model.latency_lower_bound(sweep, f) for f in factor_list]
    frontier = [i for i, b in enumerate(bounds) if b is None or b < cutoff]
    _charge_pruned(model, len(factor_list) - len(frontier))
    return bounds, frontier


def _best_candidate(s: Statement, cands: Sequence["Candidate"]
                    ) -> Optional["Candidate"]:
    """The rung argmin: feasible candidate with the lowest bottleneck-node
    latency, first strict improvement winning ties (shared by
    ``_rung_finish`` and the two-round confirm plan)."""
    best = None
    for c in cands:
        if not c.report.feasible:
            continue
        if best is None or (c.report.nodes[s.name].latency
                            < best.report.nodes[s.name].latency):
            best = c
    return best


# --------------------------------------------------------------------------
# candidate evaluation
# --------------------------------------------------------------------------
class SerialEvaluator:
    """Evaluate the rung's candidates in order on the live function —
    exactly the inner loop of the pre-subsystem greedy ladder.  When the
    rung has a closed-form sweep, each applied candidate's recurrence II
    is primed from it (``prime_recurrence_ii``), so the design report's
    II lookup is a dictionary hit; with bound pruning on
    (``POM_BOUND_PRUNE``) the sweep additionally prunes candidates whose
    latency lower bound proves they cannot win the rung."""

    def evaluate(self, ctx: SearchContext, st: LadderState, s: Statement,
                 uid: int, P: int, sweep=None, cutoff: Optional[int] = None,
                 branching: bool = False) -> List[Candidate]:
        factor_list = [tuple(f) for f in unroll_candidates(P)]
        if not (caching.bound_prune_on() and sweep is not None):
            return self.evaluate_factors(ctx, st, s, uid, factor_list, sweep)
        if cutoff is None:
            cutoff = st.report.nodes[s.name].latency
        bounds, frontier = _bound_plan(ctx.model, sweep, factor_list, cutoff)
        if branching:
            return self.evaluate_factors(
                ctx, st, s, uid, [factor_list[i] for i in frontier], sweep)
        # two-round rule: round 1 is every unbounded candidate plus the
        # lowest-bounded one, in generation order
        bounded = sorted((i for i in frontier if bounds[i] is not None),
                         key=lambda i: (bounds[i], i))
        first = [i for i in frontier if bounds[i] is None]
        if bounded:
            first = sorted(first + bounded[:1])
        rest = bounded[1:]
        pre = self.evaluate_factors(
            ctx, st, s, uid, [factor_list[i] for i in first], sweep)
        pos = {f: i for i, f in enumerate(factor_list)}
        # round 2: the rest whose bound could still beat round 1's best
        # confirmed (node latency, generation index); all of them when
        # round 1 found no feasible candidate
        best = _best_candidate(s, pre)
        if best is None:
            confirm = sorted(rest)
        else:
            lat1, i1 = best.report.nodes[s.name].latency, pos[best.factors]
            confirm = sorted(j for j in rest if bounds[j] < lat1
                             or (bounds[j] == lat1 and j < i1))
        _charge_pruned(ctx.model, len(rest) - len(confirm))
        out = self.evaluate_factors(
            ctx, st, s, uid, [factor_list[i] for i in confirm], sweep)
        return sorted(pre + out, key=lambda c: pos[c.factors])

    def evaluate_factors(self, ctx: SearchContext, st: LadderState,
                         s: Statement, uid: int,
                         factor_list: Sequence[Tuple[int, ...]],
                         sweep) -> List[Candidate]:
        """Confirm an explicit candidate subset with full design reports,
        in the given (generation) order — the pre-pruning evaluator loop.

        Each candidate restores ``s`` to its rung base and applies its
        factors, through the transformed-node memo when caching is on: a
        memo hit skips the split/permute work (and the base restore) and
        restores a schedule bit-identical to a fresh apply; the primed
        recurrence II and the partition refresh run either way."""
        fn, model = ctx.fn, ctx.model
        out: List[Candidate] = []
        base = st.base_snaps[uid]
        # the base's schedule signature (``after_spec`` is irrelevant to
        # the node-local transform)
        domain, subst, unrolls, pat, pii, _after = base
        base_key = (uid, domain.key(),
                    tuple(sorted((k, v.key()) for k, v in subst.items())),
                    tuple(sorted(unrolls.items())), pat, pii)
        t_on = telemetry.on()
        for factors in factor_list:
            factors = tuple(factors)
            key = (uid, base_key, factors)
            memo = (_APPLY_CACHE.get(key, _APPLY_MISS) if caching.ENABLED
                    else _APPLY_MISS)
            if memo is _APPLY_MISS:
                _restore_node(fn, s, base)
                ok = apply_parallel(s, factors)
            else:
                ok = memo is not None
                if ok:
                    _restore(s, memo)
            if ok:
                model.prime_recurrence_ii(s, sweep, factors)
                _refresh_partitions(fn)
            if memo is _APPLY_MISS and caching.ENABLED:
                if len(_APPLY_CACHE) >= 8192:
                    _APPLY_CACHE.clear()
                _APPLY_CACHE[key] = _snapshot(s) if ok else None
            if not ok:
                if t_on:
                    telemetry.event("stage2.candidate_illegal", _cat="dse",
                                    statement=s.name, factors=str(factors))
                continue
            if t_on:
                with telemetry.span("stage2.candidate", _cat="dse",
                                    statement=s.name,
                                    factors=str(factors)) as sp:
                    rep = ctx.design_report()
                    sp.add(feasible=rep.feasible, latency=rep.latency)
            else:
                rep = ctx.design_report()
            model.stats.confirmed_evals += 1
            out.append(Candidate(factors, rep, _snapshot(s)))
        return out


# --------------------------------------------------------------------------
# one ladder rung (shared by greedy / beam)
# --------------------------------------------------------------------------
_GUARD_MAX = 64


@dataclass
class _PendingRung:
    """Rung state carried between ``_rung_begin`` and ``_rung_finish``.

    The phase split exists for the beam's waves: a wave runs every live
    state's ``_rung_begin`` first, so states proposing an identical rung
    can share one evaluation, then evaluates and finishes each state in
    state order."""
    uid: int
    P: int
    prev: tuple                       # node snapshot at rung start
    key: Optional[Tuple] = None       # cross-state dedup key (waves only)


def _rung_begin(ctx: SearchContext, st: LadderState,
                want_key: bool = False) -> Tuple[str, Optional[_PendingRung]]:
    """Everything a rung does before candidate evaluation: termination
    checks, bottleneck selection, per-node base recording, and the
    max-parallelism exit.  Returns ``("done", None)`` when the ladder is
    finished, ``("exit", None)`` when the bottleneck hit its parallelism
    cap (state mutated, rung over), or ``("eval", pend)``."""
    st.last_rung = None
    if not st.active or st.guard >= _GUARD_MAX:
        return "done", None
    st.guard += 1
    uid = _critical_bottleneck(ctx, st)
    if uid is None:
        return "done", None
    s = ctx.by_uid[uid]
    if uid not in st.base_snaps:
        st.base_snaps[uid] = _snapshot(s)
    band_cap = 1
    for d in s.dims:
        if d not in s.unrolls:
            band_cap *= s.trip_counts().get(d, 1)
    band_cap *= st.parallel_of[uid]
    P = st.parallel_of[uid] * 2
    if P > min(ctx.max_parallel, band_cap):
        st.active.remove(uid)
        st.actions.append(f"exit {s.name}: max parallelism")
        return "exit", None
    prev = _snapshot(s)
    pend = _PendingRung(uid, P, prev)
    if want_key:
        # cross-state dedup key: the whole-design signature with the rung
        # node put back on its per-node base.  Candidates re-apply their
        # factors to that base, so two states with equal keys evaluate
        # literally identical candidate sets — the beam evaluates once and
        # credits every state that proposed it.  Signature recomputation
        # and the restore dance are memo-hit-only here (the state was just
        # live), so the key costs no counter and no analysis work.
        _restore_node(ctx.fn, s, st.base_snaps[uid])
        pend.key = (design_signature(ctx.fn), uid, P)
        _restore_node(ctx.fn, s, prev)
    return "eval", pend


def _rung_sweep(ctx: SearchContext, st: LadderState, pend: _PendingRung):
    """Per-rung closed-form ii(unroll_vector): built once from the rung
    *base* (the state candidates re-apply their factors to — the live
    state diverges from it once a rung has been accepted), it both
    pre-warms the base dependence classes/loop bounds every candidate
    transfers from and primes each applied candidate's recurrence II
    (see the evaluator), so the design report's II lookup is a hit."""
    if not caching.analytic_on():
        return None
    s = ctx.by_uid[pend.uid]
    _restore_node(ctx.fn, s, st.base_snaps[pend.uid])
    sweep = ctx.model.closed_form_ii(s)
    _restore_node(ctx.fn, s, pend.prev)
    return sweep


def _rung_finish(ctx: SearchContext, st: LadderState, pend: _PendingRung,
                 cands: List[Candidate], sweep) -> bool:
    """Accept/reject decision of one rung (the tail of the pre-split
    ``_rung``): pick the candidate that most improves the bottleneck
    *node* (first strict improvement wins ties, matching the
    pre-subsystem ladder) and accept when it does so without regressing
    the design (paper §VI-B: optimize the bottleneck, switch when it no
    longer is one)."""
    uid, P, prev = pend.uid, pend.P, pend.prev
    s = ctx.by_uid[uid]
    best = _best_candidate(s, cands)
    if (best is not None
            and best.report.nodes[s.name].latency < st.report.nodes[s.name].latency
            and best.report.latency <= st.report.latency):
        _restore_node(ctx.fn, s, best.snap)
        st.parallel_of[uid] = P
        st.report = best.report
        st.actions.append(
            f"parallel {s.name} -> {P} "
            f"(lat {st.report.nodes[s.name].latency}, "
            f"II {st.report.nodes[s.name].ii})")
        st.last_rung = RungInfo(uid, P, prev, cands, best, sweep)
    else:
        _restore_node(ctx.fn, s, prev)
        st.report = ctx.design_report()
        st.active.remove(uid)
        st.actions.append(f"exit {s.name}: no feasible improvement at P={P}")
        st.last_rung = RungInfo(uid, P, prev, cands, None, sweep)
    return True


def _rung_impl(ctx: SearchContext, st: LadderState, evaluator,
               branching: bool = False) -> bool:
    kind, pend = _rung_begin(ctx, st)
    if kind == "done":
        return False
    if kind == "exit":
        return True
    s = ctx.by_uid[pend.uid]
    sweep = _rung_sweep(ctx, st, pend)
    cands = evaluator.evaluate(ctx, st, s, pend.uid, pend.P, sweep,
                               branching=branching)
    return _rung_finish(ctx, st, pend, cands, sweep)


def _rung_telemetry(ctx: SearchContext, counts0: Dict[str, int],
                    stats0: CostStats) -> Dict[str, Any]:
    """Eval-count / cache-delta span arguments for one rung or wave —
    read-only counter arithmetic, issued only when a trace is active."""
    c = caching.counts_delta(counts0)
    d = ctx.model.stats.delta(stats0)
    return {"analysis_evals": caching.analysis_evals(c),
            "cache_hits": (c["selfdep_hits"] + c["legal_hits"]
                           + c["trip_hits"] + c["access_hits"]),
            "transfers": (c["selfdep_transfers"] + c["legal_transfers"]
                          + c["trip_transfers"]),
            "node_evals": d["node_evals"],
            "design_evals": d["design_evals"],
            "design_cache_hits": d["design_cache_hits"],
            "confirmed_evals": d["confirmed_evals"],
            "pruned_candidates": d["pruned_candidates"]}


def _rung(ctx: SearchContext, st: LadderState, evaluator,
          branching: bool = False) -> bool:
    """Advance ``st`` by one rung of the bottleneck ladder (the loop body of
    the pre-subsystem ``stage2``).  Returns False when the ladder is done.

    ``branching`` tells the evaluator whether runner-up candidates feed
    beam branching (static bound pruning only) or only the argmin matters
    (two-round pruning).

    With a trace active, the rung runs under a ``stage2.rung`` span
    carrying the bottleneck statement, target parallelism, accept/reject
    outcome, and the rung's eval-count / cache-hit deltas — all read from
    counters the rung moves anyway, never adding queries of its own."""
    if not telemetry.on():
        return _rung_impl(ctx, st, evaluator, branching)
    counts0 = dict(caching.COUNTS)
    stats0 = copy.copy(ctx.model.stats)
    with telemetry.span("stage2.rung", _cat="dse") as sp:
        more = _rung_impl(ctx, st, evaluator, branching)
        sp.add(**_rung_telemetry(ctx, counts0, stats0))
        info = st.last_rung
        if info is not None:
            s = ctx.by_uid.get(info.uid)
            sp.add(statement=s.name if s is not None else info.uid,
                   P=info.P, candidates=len(info.cands),
                   accepted=info.chosen is not None)
    return more


# --------------------------------------------------------------------------
# strategies
# --------------------------------------------------------------------------
class SearchStrategy:
    """Base of the pluggable stage-2 searchers."""
    name: str = "?"

    def run(self, ctx: SearchContext) -> LadderState:
        raise NotImplementedError

    def describe(self) -> str:
        return self.name


STRATEGIES: Dict[str, Callable[..., "SearchStrategy"]] = {}


def register_strategy(name: str):
    def deco(cls):
        STRATEGIES[name] = cls
        cls.name = name
        return cls
    return deco


@register_strategy("greedy")
class GreedySearch(SearchStrategy):
    """The paper's single-trajectory bottleneck ladder (pre-subsystem
    ``stage2``), re-expressed as rung + serial evaluator + accept rule."""

    def __init__(self, evaluator=None):
        self.evaluator = evaluator or SerialEvaluator()

    def run(self, ctx: SearchContext) -> LadderState:
        st = _init_ladder(ctx)
        st.lineage = True
        while _rung(ctx, st, self.evaluator):
            pass
        return st


@register_strategy("beam")
class BeamSearch(SearchStrategy):
    """Anchored beam search over ladder states.

    Slot 0 of the beam is pinned to the pure-greedy trajectory (its greedy
    successor always survives selection), so the final design is provably
    never worse than ``greedy``'s; the remaining ``width - 1`` slots hold
    the best other successors by design latency: runner-up candidates of
    an accepted rung and the early-exit branch (stop optimizing the
    bottleneck node, spend resources elsewhere).  With ``width=1`` the
    search degenerates to exactly the greedy trajectory.

    When several states are live, each iteration runs as a **wave**
    (``_wave``): all states' rung preambles first, then per-state
    evaluate/decide in state order.  States whose pending rung
    re-evaluates an identical ``(base design, statement, P)`` — sibling
    branches of one rung always do — share a single evaluation
    (**dedup-and-credit**, tallied in ``wave_stats``), which is what keeps
    ``beam:8`` within a small factor of ``greedy`` wall-clock.
    """

    def __init__(self, width: int = 2, evaluator=None,
                 rank: Optional[str] = None):
        self.width = max(1, int(width))
        self.evaluator = evaluator or SerialEvaluator()
        self.rank = rank or os.environ.get("POM_BEAM_RANK", "latency")
        if self.rank not in ("latency", "scalar"):
            raise ValueError(f"beam rank must be 'latency' or 'scalar', "
                             f"got {self.rank!r} (constructor, 'beam:k:rank' "
                             f"spec, or POM_BEAM_RANK)")
        self._resources: Dict = {}
        # cross-state dedup accounting, reset per run(): rungs/candidates
        # actually evaluated vs credited from an identical sibling rung
        self.wave_stats: Dict[str, int] = {}

    def describe(self) -> str:
        out = f"beam:{self.width}"
        if self.rank != "latency":
            out += f":{self.rank}"
        return out

    def _rank_value(self, state: LadderState):
        """Beam-retention rank of a successor state.

        ``latency`` (default) keeps the PR-3 behavior; ``scalar`` ranks by
        a latency x resource scalarization over the Pareto axes
        (``DesignReport.resource_vector``), so the non-anchored slots
        prefer designs that buy their latency with fewer DSPs/BRAMs and
        keep headroom for later rungs.  The anchored greedy slot and the
        final state selection stay latency-based, which preserves the
        cost <= greedy guarantee under either ranking.
        """
        rep = state.report
        if self.rank == "latency":
            return rep.latency
        dsp_cap = max(1, self._resources.get("dsp", 1))
        bram18_cap = max(1.0, self._resources.get("bram_bits", 18_000.0)
                         / 18_000.0)
        dsp, bram18 = rep.resource_vector
        return rep.latency * (1.0 + dsp / dsp_cap + bram18 / bram18_cap)

    def run(self, ctx: SearchContext) -> LadderState:
        self._resources = ctx.model.resources
        self.wave_stats = {"rungs_evaluated": 0, "rungs_credited": 0,
                           "cands_evaluated": 0, "cands_credited": 0}
        st = _init_ladder(ctx)
        st.lineage = True
        st.snap = _snapshot_fn(ctx.fn)
        st.sig = design_signature(ctx.fn)
        live, done = [st], []
        while live:
            if len(live) == 1:
                successors = self._step_single(ctx, live[0], done)
            else:
                successors = self._wave(ctx, live, done)
            live = self._select(successors)
        # unify the per-run dedup tallies into the metrics registry
        telemetry.merge_counters(self.wave_stats, prefix="search.wave.")
        best = min(enumerate(done),
                   key=lambda t: (t[1].report.latency,
                                  0 if t[1].lineage else 1, t[0]))[1]
        _restore_fn(ctx.fn, best.snap)
        return best

    def _step_single(self, ctx: SearchContext, cur: LadderState,
                     done: List[LadderState]
                     ) -> List[Tuple[int, LadderState]]:
        """One iteration with a single live state: the plain rung path
        (with ``width=1`` this is exactly the greedy trajectory)."""
        successors: List[Tuple[int, LadderState]] = []
        _restore_fn(ctx.fn, cur.snap)
        pre = cur.clone()
        pre.lineage = False
        progressed = _rung(ctx, cur, self.evaluator,
                           branching=self.width > 1)
        if not progressed:
            done.append(cur)
            return successors
        ws = self.wave_stats
        if cur.last_rung is not None:
            ws["rungs_evaluated"] += 1
            ws["cands_evaluated"] += len(unroll_candidates(cur.last_rung.P))
        cur.snap = _snapshot_fn(ctx.fn)
        cur.sig = design_signature(ctx.fn)
        successors.append((0, cur))
        seq = 1
        if self.width > 1 and cur.last_rung is not None:
            for alt in self._branches(ctx, pre, cur.last_rung):
                successors.append((seq, alt))
                seq += 1
        return successors

    def _wave(self, ctx: SearchContext, live: List[LadderState],
              done: List[LadderState]) -> List[Tuple[int, LadderState]]:
        """Traced wrapper of :meth:`_wave_impl`: a ``stage2.wave`` span
        carrying live-state count, dedup credits, and eval-count deltas
        for this wave (read-only; absent overhead when tracing is off)."""
        if not telemetry.on():
            return self._wave_impl(ctx, live, done)
        ws0 = dict(self.wave_stats)
        counts0 = dict(caching.COUNTS)
        stats0 = copy.copy(ctx.model.stats)
        with telemetry.span("stage2.wave", _cat="dse",
                            states=len(live)) as sp:
            out = self._wave_impl(ctx, live, done)
            sp.add(**_rung_telemetry(ctx, counts0, stats0))
            sp.add(**{k: v - ws0.get(k, 0)
                      for k, v in self.wave_stats.items()})
        return out

    def _wave_impl(self, ctx: SearchContext, live: List[LadderState],
                   done: List[LadderState]
                   ) -> List[Tuple[int, LadderState]]:
        """One beam iteration over several live states, in two phases.

        Phase A (state order): run every state's rung preamble
        (``_rung_begin``) and compute its cross-state dedup key — all
        memo-hit work, no counters move.  Phase B (state order): for each
        state, either **credit** a rung an earlier state in this wave
        already evaluated (identical key ⇒ literally identical candidate
        sets, reports and snapshots — sibling branches of one rung always
        collide here), or charge the sweep and evaluate the rung; then
        decide accept/reject and branch exactly as the single-state path
        does."""
        successors: List[Tuple[int, LadderState]] = []
        seq = 0
        plans = []
        for cur in live:
            _restore_fn(ctx.fn, cur.snap)
            pre = cur.clone()
            kind, pend = _rung_begin(ctx, cur, want_key=True)
            plans.append((cur, pre, kind, pend))
        # bound-and-confirm: sibling states sharing a rung key may sit at
        # different pre-rung bottleneck latencies; the shared evaluation
        # must confirm the union of what every proposer needs, so the
        # per-key cutoff is the MAX over proposing states (a superset
        # frontier — still only provable losers are pruned)
        key_cutoff: Dict = {}
        if caching.bound_prune_on():
            for cur, _, kind, pend in plans:
                if kind != "eval":
                    continue
                s = ctx.by_uid[pend.uid]
                c = cur.report.nodes[s.name].latency
                old = key_cutoff.get(pend.key)
                key_cutoff[pend.key] = c if old is None or c > old else old
        ws = self.wave_stats
        shared: Dict = {}
        for cur, pre, kind, pend in plans:
            if kind == "done":
                done.append(cur)
                continue
            if kind == "exit":
                # schedule untouched: keep snap/sig; no last_rung, so no
                # branches — same successor the single-state path yields
                successors.append((seq, cur))
                seq += 1
                continue
            _restore_fn(ctx.fn, cur.snap)
            n_cands = len(unroll_candidates(pend.P))
            hit = shared.get(pend.key)
            if hit is not None:
                sweep, cands = hit
                ws["rungs_credited"] += 1
                ws["cands_credited"] += n_cands
            else:
                sweep = _rung_sweep(ctx, cur, pend)
                cands = self.evaluator.evaluate(
                    ctx, cur, ctx.by_uid[pend.uid], pend.uid, pend.P, sweep,
                    cutoff=key_cutoff.get(pend.key), branching=True)
                shared[pend.key] = (sweep, cands)
                ws["rungs_evaluated"] += 1
                ws["cands_evaluated"] += n_cands
            _rung_finish(ctx, cur, pend, cands, sweep)
            cur.snap = _snapshot_fn(ctx.fn)
            cur.sig = design_signature(ctx.fn)
            successors.append((seq, cur))
            seq += 1
            if self.width > 1 and cur.last_rung is not None:
                for alt in self._branches(ctx, pre, cur.last_rung):
                    successors.append((seq, alt))
                    seq += 1
        return successors

    # -- branching ----------------------------------------------------------
    def _branches(self, ctx: SearchContext, pre: LadderState,
                  info: RungInfo) -> List[LadderState]:
        """Alternative successors of one rung, built from the evaluations
        the rung already paid for (no extra model calls beyond cache hits)."""
        out: List[LadderState] = []
        s = ctx.by_uid[info.uid]
        for c in info.cands:
            if info.chosen is not None and c is info.chosen:
                continue
            if not c.report.feasible:
                continue
            if c.report.latency > pre.report.latency:
                continue
            if (c.report.nodes[s.name].latency
                    >= pre.report.nodes[s.name].latency):
                continue
            alt = pre.clone()
            alt.guard = pre.guard + 1
            # the rung added base_snaps[uid] to the greedy successor AFTER
            # `pre` was cloned; alts must carry the same clean per-node
            # base (info.prev == the clean state on a first visit), or a
            # later rung would re-split on top of this candidate's splits
            alt.base_snaps.setdefault(info.uid, info.prev)
            _restore_fn(ctx.fn, pre.snap)
            _restore_node(ctx.fn, s, c.snap)
            alt.parallel_of[info.uid] = info.P
            alt.report = c.report
            alt.actions.append(
                f"parallel {s.name} -> {info.P} "
                f"(lat {c.report.nodes[s.name].latency}, "
                f"II {c.report.nodes[s.name].ii}) [beam-alt {c.factors}]")
            alt.snap = _snapshot_fn(ctx.fn)
            alt.sig = design_signature(ctx.fn)
            out.append(alt)
        if info.chosen is not None:
            # early-exit branch: keep the node at its current parallelism
            # and let the ladder move to the next bottleneck
            alt = pre.clone()
            alt.guard = pre.guard + 1
            alt.active = [u for u in alt.active if u != info.uid]
            alt.actions.append(f"exit {s.name}: beam early-exit at "
                               f"P={pre.parallel_of[info.uid]}")
            alt.snap = pre.snap
            alt.sig = pre.sig
            out.append(alt)
        return out

    # -- selection ----------------------------------------------------------
    def _select(self, successors: List[Tuple[int, LadderState]]
                ) -> List[LadderState]:
        if not successors:
            return []
        keep: List[LadderState] = []
        seen: set = set()

        def key_of(state: LadderState) -> Tuple:
            return (state.sig, tuple(sorted(state.active)),
                    tuple(sorted(state.parallel_of.items())))

        anchored = [s for _, s in successors if s.lineage]
        if anchored:
            keep.append(anchored[0])
            seen.add(key_of(anchored[0]))
        ranked = sorted(((self._rank_value(s), seq, s)
                         for seq, s in successors if not s.lineage),
                        key=lambda t: (t[0], t[1]))
        for _, _, s in ranked:
            if len(keep) >= self.width:
                break
            k = key_of(s)
            if k in seen:
                continue
            seen.add(k)
            keep.append(s)
        return keep


# --------------------------------------------------------------------------
# strategy resolution + entry point
# --------------------------------------------------------------------------
def resolve_strategy(spec=None, beam_width: Optional[int] = None
                     ) -> SearchStrategy:
    """Turn a strategy spec into a strategy instance.

    ``spec`` may be a :class:`SearchStrategy`, a registered name
    (``"greedy"``, ``"beam"``), or a parameterized name.  The beam grammar
    is ``beam[:k][:latency|scalar]`` with the segments in either order —
    ``"beam:4"``, ``"beam:scalar"``, ``"beam:4:scalar"``,
    ``"beam:scalar:4"`` are all valid; a duplicate or unknown segment is a
    ``ValueError`` naming the original spec.

    Precedence when ``spec`` is None: ``beam_width`` selects ``beam``
    (the call site is more explicit than ``POM_DSE_STRATEGY``); otherwise
    the ``POM_DSE_STRATEGY`` environment variable (same syntax) decides;
    otherwise ``greedy``.  When both a ``beam`` spec and ``beam_width``
    are given, ``beam_width`` overrides the spec's ``:k``.  A ``:k``
    suffix on a strategy that takes no parameter is an error, reported
    against the original spec.
    """
    if isinstance(spec, SearchStrategy):
        return spec
    if isinstance(spec, type) and issubclass(spec, SearchStrategy):
        return spec()
    if spec is None:
        spec = ("beam" if beam_width is not None
                else os.environ.get("POM_DSE_STRATEGY") or "greedy")
    name, _, arg = str(spec).partition(":")
    if name not in STRATEGIES:
        raise ValueError(f"unknown DSE strategy {name!r} "
                         f"(registered: {sorted(STRATEGIES)})")
    if name == "beam":
        width = rank = None
        for t in (t for t in arg.split(":") if t):
            if t.lstrip("-").isdigit():
                if width is not None:
                    raise ValueError(f"duplicate beam width {t!r} in "
                                     f"{spec!r}")
                width = int(t)
            elif t in ("latency", "scalar"):
                if rank is not None:
                    raise ValueError(f"duplicate beam rank {t!r} in "
                                     f"{spec!r}")
                rank = t
            else:
                raise ValueError(
                    f"bad beam spec segment {t!r} in {spec!r} (want "
                    f"beam[:k][:latency|scalar])")
        if beam_width is not None:
            width = beam_width
        return BeamSearch(width=2 if width is None else width, rank=rank)
    if arg:
        raise ValueError(f"strategy {name!r} takes no ':{arg}' parameter "
                         f"(got {spec!r})")
    return STRATEGIES[name]()


def _dataflow_step(ctx: SearchContext, st: LadderState) -> None:
    """Stage-2 dataflow search dimension: evaluate the final design under
    both aggregations — sequential and task-pipelined — archive both
    points (latency vs channel-BRAM trade-off), and pin the winner on the
    function (``fn.dataflow``), so downstream codegen emits exactly the
    schedule the search chose.

    An explicit ``fn.dataflow = True`` pin (``auto_dse(dataflow=True)``,
    DSL toggle, or ``HlsModel(dataflow=True)``) is honored: the step
    records both archive points but never un-pins the function — codegen
    then emits the requested region even when the model judged the
    overlap not beneficial.

    Skipped entirely (zero model/analysis calls) when dataflow is off for
    the function (``POM_DATAFLOW=0`` or an explicit ``dataflow=False``) or
    the design has fewer than two tasks — which is what keeps the
    dataflow-off engine bit-identical to the sequential one."""
    from .graph_ir import dataflow_effective, fusion_tasks
    fn = ctx.fn
    if not dataflow_effective(fn):
        return
    if len(fusion_tasks(fn)) < 2:
        return
    pinned = fn.dataflow is True
    prev = fn.dataflow
    try:
        fn.dataflow = False
        rep_off = ctx.design_report()
        fn.dataflow = True
        rep_on = ctx.design_report()
    except Exception:
        fn.dataflow = prev
        raise
    applied = rep_on.dataflow is not None and rep_on.dataflow.applied
    if pinned or (applied and rep_on.latency < rep_off.latency and (
            rep_on.feasible or not rep_off.feasible)):
        fn.dataflow = True
        st.report = rep_on
        d = rep_on.dataflow
        kinds = ",".join(f"{c[0]}:{c[3]}" for c in (d.channels if d else ()))
        st.actions.append(
            f"dataflow on{' (pinned)' if pinned and not applied else ''}: "
            f"lat {rep_on.latency} vs {rep_off.latency} "
            f"sequential (+{rep_on.bram18 - rep_off.bram18} bram18; "
            f"channels {kinds or 'none'})")
    else:
        fn.dataflow = False
        st.report = rep_off
        reason = ("not beneficial" if rep_on.dataflow is None
                  else rep_on.dataflow.reason or "not beneficial")
        st.actions.append(f"dataflow off: {reason}")


def run_stage2(fn: Function, model: Optional[HlsModel] = None,
               max_parallel: int = 256,
               actions: Optional[List[str]] = None,
               strategy=None, archive: Optional[ParetoArchive] = None,
               beam_width: Optional[int] = None) -> DesignReport:
    """Stage-2 entry point: run the selected search strategy, then the
    dataflow on/off decision step (``_dataflow_step``).

    This is what ``dse.stage2`` and the stage-2 pipeline passes call; with
    the default (greedy) strategy — and dataflow off — it is bit-identical
    — schedules, reports, action logs, evaluation counters — to the
    pre-subsystem ladder.
    """
    model = model or HlsModel()
    # a model-level dataflow override is materialized on the function so
    # the search decision, the Pareto-archive signatures, and downstream
    # codegen all agree with what the evaluator actually modeled
    if fn.dataflow is None and model._dataflow_flag is not None:
        fn.dataflow = bool(model._dataflow_flag)
    strat = resolve_strategy(strategy, beam_width=beam_width)
    ctx = SearchContext(fn=fn, model=model, max_parallel=max_parallel,
                        archive=archive, strategy_name=strat.describe())
    st = strat.run(ctx)
    _dataflow_step(ctx, st)
    if actions is not None:
        actions.extend(st.actions)
    return st.report
