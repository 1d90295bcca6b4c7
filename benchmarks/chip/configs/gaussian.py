"""The POM paper's 3x3 Gaussian blur, for the configurations that name
``"program": "gaussian"`` (``gaussian_4096.json``).

``program()`` is the DSL program (a copy of ``benchmarks/workloads.py``'s
``gaussian``). ``reference()`` and ``control()`` are plain ``jax.numpy``
and import nothing of the program.
"""
import jax
import jax.numpy as jnp


def program(config, schedule):
    """(DSL function, extra ``pom.compile`` options) for ``schedule``."""
    from repro.core import dsl as pom
    n = config["n"]
    with pom.function("gaussian") as f:
        i, j = pom.var("i", 1, n - 1), pom.var("j", 1, n - 1)
        img = pom.placeholder("img", (n, n))
        out = pom.placeholder("out", (n, n))
        pom.compute("g", [i, j],
                    0.0625 * (img(i - 1, j - 1) + 2.0 * img(i - 1, j)
                              + img(i - 1, j + 1) + 2.0 * img(i, j - 1)
                              + 4.0 * img(i, j) + 2.0 * img(i, j + 1)
                              + img(i + 1, j - 1) + 2.0 * img(i + 1, j)
                              + img(i + 1, j + 1)), out(i, j))
    return f, config["schedules"][schedule].get("compile", {})


def inputs(key, config, traffic):
    """``img`` and ``out``, with a leading axis of the traffic's ``batch``
    lanes where it names one. ``out`` starts random too, so a border the
    program must leave alone is checked."""
    n = config["n"]
    ki, ko = jax.random.split(key)
    shape = ((traffic["batch"],) if "batch" in traffic else ()) + (n, n)
    return {"img": jax.random.normal(ki, shape, jnp.float32),
            "out": jax.random.normal(ko, shape, jnp.float32)}


def _blur(x, out):
    g = 0.0625 * (x[:-2, :-2] + 2.0 * x[:-2, 1:-1] + x[:-2, 2:]
                  + 2.0 * x[1:-1, :-2] + 4.0 * x[1:-1, 1:-1]
                  + 2.0 * x[1:-1, 2:] + x[2:, :-2] + 2.0 * x[2:, 1:-1]
                  + x[2:, 2:])
    return out.at[1:-1, 1:-1].set(g.astype(out.dtype))


def reference(a, config):
    """Every array the program writes, in float32."""
    return {"out": _blur(a["img"], a["out"])}


def control(a, config):
    """The reference computed in bfloat16."""
    bf = jnp.bfloat16
    out = _blur(a["img"].astype(bf), a["out"].astype(bf))
    return {"out": out.astype(jnp.float32)}


def work(config, traffic):
    """Algorithmic FLOPs and minimum HBM bytes of one lane: 8 adds and 6
    multiplies per interior point; ``img`` read once and the interior of
    ``out`` written once (the border is left as it is)."""
    n = config["n"]
    m = (n - 2) ** 2
    return {"total": {"flops": 14 * m, "bytes": 4 * n * n + 4 * m}}
