"""PolyBench gemm, ``C += A @ B``, for the configurations that name
``"program": "gemm"`` (``gemm_4096.json``).

``program()`` is the DSL program (a copy of ``benchmarks/workloads.py``'s
``gemm``, so later edits there cannot move the yardstick) with its named
schedules. ``reference()`` and ``control()`` are plain ``jax.numpy`` and
import nothing of the program.
"""
import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def program(config, schedule):
    """(DSL function, extra ``pom.compile`` options) for ``schedule``."""
    from repro.core import dsl as pom
    n = config["n"]
    with pom.function("gemm") as f:
        i, j, k = pom.var("i", 0, n), pom.var("j", 0, n), pom.var("k", 0, n)
        A = pom.placeholder("A", (n, n))
        B = pom.placeholder("B", (n, n))
        C = pom.placeholder("C", (n, n))
        pom.compute("s", [i, j, k], C(i, j) + A(i, k) * B(k, j), C(i, j))
    params = config["schedules"][schedule]
    if schedule == "tiled":
        # (ti, tj, tk) tiles with the intra-tile loops unrolled: the
        # blocks of the contraction kernel
        ti, tj, tk = params["tiles"]
        s = f.stmt("s")
        s.tile("i", "j", ti, tj, "i0", "j0", "i1", "j1")
        s.split("k", tk, "k0", "k1")
        s.unroll("i1", ti).unroll("j1", tj).unroll("k1", tk)
        s.pipeline("k0", 1)
    return f, params.get("compile", {})


def inputs(key, config, traffic):
    """``A``, ``B`` and ``C``, with a leading axis of the traffic's
    ``batch`` lanes where it names one."""
    n = config["n"]
    ka, kb, kc = jax.random.split(key, 3)
    shape = ((traffic["batch"],) if "batch" in traffic else ()) + (n, n)
    return {"A": jax.random.normal(ka, shape, jnp.float32),
            "B": jax.random.normal(kb, shape, jnp.float32),
            "C": jax.random.normal(kc, shape, jnp.float32)}


def reference(a, config):
    """Every array the program writes, in float32 at 'highest'."""
    return {"C": a["C"] + jnp.matmul(a["A"], a["B"], precision=HIGHEST)}


def control(a, config):
    """The reference with its operands rounded to float8_e4m3 (4 exponent,
    3 mantissa bits) by ``reduce_precision``: on a TPU v5e a round trip
    through ``float8_e4m3fn`` before this dot read the same error as one
    bfloat16 pass, so the rounding is made explicit."""
    def q(x):
        return jax.lax.reduce_precision(x, exponent_bits=4, mantissa_bits=3)
    return {"C": a["C"] + jnp.matmul(q(a["A"]), q(a["B"]), precision=HIGHEST)}


def work(config, traffic):
    """Algorithmic FLOPs and minimum HBM bytes of one lane: a multiply and
    an add per (i, j, k); A, B and C read once, C written once."""
    n = config["n"]
    one = {"flops": 2 * n ** 3, "bytes": 4 * 4 * n * n}
    return {"total": one, "contraction": one}
