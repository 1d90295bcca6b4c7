#!/usr/bin/env python3
"""Faults planted in the granite decode step, read by the cell's own
``correct`` comparison at any size.

  python3 benchmarks/chip/tests/granite_faults.py \\
      --workload granite_4_0_h_micro.decode_32x4k --seed <n>

For one seed, in one process: the cell's inputs and its reference, then
the entry's ``max_rel_err.logits`` against it, sound and with each fault
of ``FAULTS`` planted under the timed path (one entry built at a time,
the prefill sound). Prints one line a fault and a JSON summary last.
``test_granite_decode.py`` plants the same faults at reduced widths.
"""
import argparse
import contextlib
import gc
import json
import sys
from pathlib import Path
from unittest import mock

ROOT = Path(__file__).resolve().parents[3]
for _p in (str(ROOT), str(ROOT / "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import repro.models  # noqa: E402
from repro.kernels import ops  # noqa: E402


def _step(before=None, after=None):
    """``repro.models.decode_step`` with ``before(cache, pos) -> (cache,
    pos)`` applied to its inputs and ``after(logits)`` to its logits."""
    original = repro.models.decode_step

    def broken(params, cfg, cache, token, pos):
        if before is not None:
            cache, pos = before(cache, pos)
        logits, cache = original(params, cfg, cache, token, pos)
        return (logits if after is None else after(logits)), cache
    return mock.patch.object(repro.models, "decode_step", broken)


def _kernel(change):
    """``ops.decode_attention`` handed ``change(k, length, layer) ->
    (length, layer)`` in place of the step's own length and layer."""
    original = ops.decode_attention

    def broken(q, k, v, *, length=None, layer=None, **kw):
        length, layer = change(k, length, layer)
        return original(q, k, v, length=length, layer=layer, **kw)
    return mock.patch.object(ops, "decode_attention", broken)


def _zeroed(*kinds):
    def before(cache, pos):
        return ({n: {k: jnp.zeros_like(c) if k in kinds else c
                     for k, c in layer.items()}
                 for n, layer in cache.items()}, pos)
    return lambda: _step(before=before)


def _half_the_requests(logits):
    keep = jnp.arange(logits.shape[0]) < logits.shape[0] // 2
    return jnp.where(keep[:, None], logits, 0.0)


# name -> a context manager that plants the fault while it is open
FAULTS = {
    # the last token decoded at row 0: a position that was never advanced
    "position_reset": lambda: _step(
        before=lambda cache, pos: (cache, jnp.zeros_like(pos))),
    # a cache the prefill never filled, in each of its three kinds
    "zeroed_kv": _zeroed("k", "v"),
    "zeroed_ssm_state": _zeroed("h"),
    "zeroed_conv_window": _zeroed("conv"),
    # the decode kernel reads the next attention layer's K/V of the stack
    "wrong_layer": lambda: _kernel(
        lambda k, n, lay: (n, (lay + 1) % k.shape[0])),
    # the kernel reads the first half of each request's rows: a length
    # left from before the prefill's second half was written
    "stale_length": lambda: _kernel(lambda k, n, lay: (n // 2, lay)),
    "half_the_requests": lambda: _step(after=_half_the_requests),
}


def readings(cell, seed, log=print):
    """{"sound": err, fault: err, ...}: the worst ``max_rel_err`` of the
    entry's outputs against the reference, one entry built a fault."""
    from benchmarks.chip import harness
    sets = harness.make_inputs(cell, seed)
    ref = jax.block_until_ready(harness.reference_fn(cell)(sets[0]))
    out = {}
    for name in ["sound", *FAULTS]:
        with (contextlib.nullcontext() if name == "sound"
              else FAULTS[name]()):
            entry = harness.build_entry(cell, sets)
            got = jax.block_until_ready(entry(sets[0]))
        out[name] = max(harness.max_rel_err(got[k], ref[k]) for k in got)
        del entry, got
        gc.collect()
        log(f"seed {seed} {name}: max_rel_err {out[name]!r} "
            f"(limit {cell.config['limits']['max_rel_err']})")
    return out


def main(argv=None) -> int:
    from benchmarks.chip import harness
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    args = ap.parse_args(argv)
    cell = harness.resolve(harness.load_spec(), args.workload)
    harness.configure_jax()
    got = readings(cell, args.seed)
    limit = cell.config["limits"]["max_rel_err"]
    print(json.dumps({"workload": args.workload, "seed": args.seed,
                      "limit": limit, "max_rel_err": got,
                      "caught": {n: e > limit for n, e in got.items()
                                 if n != "sound"}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
