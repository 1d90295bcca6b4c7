"""Global switch + counters for POM's incremental-evaluation layer.

Every memoization cache in the analysis/search stack (composed accesses and
trip counts in ``ir.py``, ``self_dependences``/``_legal`` in
``transforms.py``, ``DepGraph.paths`` in ``depgraph.py``, per-node and
whole-design cost reports in ``cost_model.py``, partition contributions in
``dse.py``, kernel lowering in ``backend_pallas.py``) consults
``caching.ENABLED``.  Disabling it restores the pre-incremental engine
exactly: all results are recomputed from scratch on every query.

Cache keys are *structural signatures* recomputed from the current schedule
state on every lookup — never version counters — so a cache can return a
stale value only if two different schedule states produce the same
signature, which the signature definitions rule out by construction.  This
is what makes cached and uncached runs bit-for-bit identical (asserted by
``tests/test_incremental_dse.py``).

``COUNTS`` tracks evaluation/hit counters for the polyhedral layer; the
cost-model layer keeps its own per-model ``CostStats`` (a shared model can
be handed to ``auto_dse`` to read them back).
"""
from __future__ import annotations

import os
from contextlib import contextmanager
from typing import Dict

ENABLED: bool = True

# Analytic dependence transfer (change-of-basis algebra on dependence
# vectors, ``affine.BasisMap``): when on, transforms push cached
# dependence/trip/legality facts through the basis map they apply instead
# of letting the next query re-derive them by Fourier–Motzkin.  The
# transfer layer rides on the signature-keyed caches, so it is only active
# when ``ENABLED`` is also true; ``POM_ANALYTIC_TRANSFER=0`` restores the
# exact (FM-only) engine.  Transfers that cannot be performed exactly fall
# back to FM automatically, which is what keeps analytic and exact runs
# bit-identical.
ANALYTIC: bool = os.environ.get("POM_ANALYTIC_TRANSFER", "1") != "0"

# Bound-and-confirm rung evaluation (branch-and-bound over a rung's
# candidate set): when on, the evaluator orders candidates by an admissible
# closed-form latency lower bound and confirm with a full ``node_report``
# only those whose bound could still beat the best confirmed bottleneck
# latency.  The bound rides on the same ``ClosedFormII`` sweep the analytic
# layer builds per rung, so pruning is only active when ``analytic_on()``;
# ``POM_BOUND_PRUNE=0`` restores exhaustive per-candidate evaluation.
# Selected designs/actions/reports are bit-identical either way — pruning
# only skips candidates whose bound proves they cannot win the rung.
BOUND_PRUNE: bool = os.environ.get("POM_BOUND_PRUNE", "1") != "0"

COUNTS: Dict[str, int] = {
    "selfdep_evals": 0, "selfdep_hits": 0, "selfdep_transfers": 0,
    "legal_evals": 0, "legal_hits": 0, "legal_transfers": 0,
    "trip_evals": 0, "trip_hits": 0, "trip_transfers": 0,
    "access_evals": 0, "access_hits": 0,
}


def set_enabled(value: bool) -> None:
    global ENABLED
    ENABLED = bool(value)


def set_analytic(value: bool) -> None:
    global ANALYTIC
    ANALYTIC = bool(value)


def analytic_on() -> bool:
    """Analytic transfer is layered on the incremental caches."""
    return ENABLED and ANALYTIC


def set_bound_prune(value: bool) -> None:
    global BOUND_PRUNE
    BOUND_PRUNE = bool(value)


def bound_prune_on() -> bool:
    """Bound-and-confirm pruning is layered on the analytic sweep."""
    return analytic_on() and BOUND_PRUNE


def reset_counts() -> None:
    for k in COUNTS:
        COUNTS[k] = 0


def analysis_evals(counts: Dict[str, int] = None) -> int:
    """The headline incremental-analysis work metric: polyhedral
    self-dependence + legality + trip-count evaluations (cache hits and
    analytic transfers excluded).  One definition shared by the perf-smoke
    budgets, ``bench_dse_speed --check``, and telemetry snapshots."""
    c = COUNTS if counts is None else counts
    return c["selfdep_evals"] + c["legal_evals"] + c["trip_evals"]


def clear_all() -> None:
    """Empty every process-global memo table (benchmark hygiene: measure a
    workload from a cold cache).  Per-statement / per-model caches die with
    their owning objects and need no clearing here."""
    import sys

    from .affine import _DEPVEC_CACHE, _INTERN
    from .ir import _TRIP_CANON_CACHE
    from .transforms import _LEGAL_CACHE
    from .cost_model import _REC_II_CACHE
    _DEPVEC_CACHE.clear()
    _INTERN.clear()
    _TRIP_CANON_CACHE.clear()
    _LEGAL_CACHE.clear()
    _REC_II_CACHE.clear()
    from .graph_ir import (_EDGE_CACHE, _FUSION_CACHE, _SKELETON_CACHE,
                           _TASKGRAPH_CACHE)
    _TASKGRAPH_CACHE.clear()
    _EDGE_CACHE.clear()
    _SKELETON_CACHE.clear()
    _FUSION_CACHE.clear()
    from .search import _APPLY_CACHE
    _APPLY_CACHE.clear()
    from .dse import _REFRESH_CACHE
    _REFRESH_CACHE.clear()
    # don't *import* the pallas backend (pulls in jax) just to clear it
    pallas = sys.modules.get("repro.core.backend_pallas")
    if pallas is not None:
        pallas._LOWER_CACHE.clear()


def counts_delta(before: Dict[str, int]) -> Dict[str, int]:
    return {k: COUNTS[k] - before.get(k, 0) for k in COUNTS}


@contextmanager
def counting_paused():
    """Run a block without perturbing the evaluation counters.

    The pipeline's per-stage verifiers re-run legality / dependence /
    bound queries that the search has (in the cached engine) already
    computed; the counters exist to measure *candidate-evaluation* work,
    so verification must not shift them.  Counter state is snapshotted and
    restored; cache contents are untouched.  Each verifier runs *after*
    the stage that issues its queries (and search candidates always differ
    structurally from stage-boundary schedules), so verifier-warmed
    entries are never what turns a later genuine evaluation into a hit.
    """
    snap = dict(COUNTS)
    try:
        yield
    finally:
        COUNTS.clear()
        COUNTS.update(snap)


@contextmanager
def disabled():
    """Run a block with every incremental cache bypassed (baseline engine)."""
    global ENABLED
    prev = ENABLED
    ENABLED = False
    try:
        yield
    finally:
        ENABLED = prev


@contextmanager
def analytic_disabled():
    """Run a block on the exact (FM-only) engine: caches stay on, but every
    dependence/trip/legality/II fact is re-derived polyhedrally instead of
    transferred through the change-of-basis algebra."""
    global ANALYTIC
    prev = ANALYTIC
    ANALYTIC = False
    try:
        yield
    finally:
        ANALYTIC = prev


@contextmanager
def bound_prune_disabled():
    """Run a block with exhaustive rung evaluation: every candidate gets a
    full ``node_report``, no bound ordering, no early stop — the reference
    engine the bound-and-confirm bit-identity tests compare against."""
    global BOUND_PRUNE
    prev = BOUND_PRUNE
    BOUND_PRUNE = False
    try:
        yield
    finally:
        BOUND_PRUNE = prev


@contextmanager
def enabled():
    global ENABLED
    prev = ENABLED
    ENABLED = True
    try:
        yield
    finally:
        ENABLED = prev
