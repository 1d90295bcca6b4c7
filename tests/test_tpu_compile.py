"""Compile the main path for a described TPU v5e, with no chip attached.

The TPU compiler is installed with jax; it compiles for a ``v5e:2x2``
topology that is described, not attached, and refuses what the chip
would refuse: blocks that break the (8, 128) tiling, kernels over the
VMEM budget, programs over HBM.  Nothing runs, so these tests say nothing
about results or times.

The topology is described in a module-scoped fixture, never at import:
only one process may load the TPU library, and under pytest-xdist every
worker imports every test file.  Where the topology cannot be described
the fixture skips.  ``pallas_interpret()`` sees the CPU here, so each
test asks for the compiled kernels itself.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, SingleDeviceSharding

from benchmarks import workloads
from repro.core.pipeline import compile as pcompile

KERNEL = 'custom_call_target="tpu_custom_call"'


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _step_text(prog, sharding) -> str:
    assert prog.traceable(), prog._trace_error
    spec = {ph.name: _sds(ph.shape, jnp.float32, sharding)
            for ph in prog.fn.placeholders.values()}
    return jax.jit(prog._step).lower(spec).compile().as_text()


def test_tiled_gemm_4096_compiles_to_a_kernel(one_chip):
    f = workloads.gemm(4096)
    s = f.stmt("s")
    s.tile("i", "j", 256, 256, "i0", "j0", "i1", "j1")
    s.split("k", 512, "k0", "k1")
    s.unroll("i1", 256).unroll("j1", 256).unroll("k1", 512)
    prog = pcompile(f.fn, target="pallas", interpret=False)
    assert _step_text(prog, one_chip).count(KERNEL) == 1


def test_kernel_and_its_ops_carry_the_statement_name(one_chip):
    """The contraction kernel is named for its statement, and the step's
    ops sit under a named scope of the statement that made them."""
    f = workloads.gemm(1024)
    s = f.stmt("s")
    s.tile("i", "j", 256, 256, "i0", "j0", "i1", "j1")
    s.split("k", 512, "k0", "k1")
    s.unroll("i1", 256).unroll("j1", 256).unroll("k1", 512)
    prog = pcompile(f.fn, target="pallas", interpret=False)
    (kernel,) = [line for line in _step_text(prog, one_chip).splitlines()
                 if KERNEL in line]
    assert kernel.lstrip().startswith("%s.")
    assert 'op_name="jit(step)/s/' in kernel


def test_untiled_gemm_4096_compiles_without_a_kernel(one_chip):
    """(1, 1) blocks would break the tiling: the nest vectorizes instead."""
    prog = pcompile(workloads.gemm(4096).fn, target="pallas",
                    interpret=False)
    assert KERNEL not in _step_text(prog, one_chip)


def test_gaussian_4096_step_has_no_gather_scatter_or_sort(one_chip):
    """The stencil's taps and its store are unit-stride windows: the
    chip's compiler gets slices and a dynamic-update-slice, no gather,
    scatter or sort."""
    prog = pcompile(workloads.gaussian(4096).fn, target="pallas",
                    interpret=False)
    text = _step_text(prog, one_chip)
    assert " dynamic-update-slice(" in text
    for op in ("gather", "scatter", "sort"):
        assert f" {op}(" not in text, op


SMOLLM = dict(b=4, hq=15, hkv=5, d=64, s=256)


def test_decode_attention_compiles_at_smollm_width(one_chip):
    from repro.kernels.decode_attention import decode_attention
    b, hq, hkv, d, s = (SMOLLM[k] for k in ("b", "hq", "hkv", "d", "s"))
    bf = jnp.bfloat16
    f = jax.jit(lambda q, k, v, n: decode_attention(
        q, k, v, length=n, bkv=128, interpret=False))
    c = f.lower(_sds((b, hq, d), bf, one_chip),
                _sds((b, hkv, s, d), bf, one_chip),
                _sds((b, hkv, s, d), bf, one_chip),
                _sds((b,), jnp.int32, one_chip)).compile()
    assert c.as_text().count(KERNEL) == 1


def test_flash_attention_compiles_at_smollm_width(one_chip):
    from repro.kernels.flash_attention import flash_attention
    b, hq, hkv, d, s = (SMOLLM[k] for k in ("b", "hq", "hkv", "d", "s"))
    bf = jnp.bfloat16
    f = jax.jit(lambda q, k, v: flash_attention(
        q, k, v, causal=True, bq=128, bkv=128, interpret=False))
    c = f.lower(_sds((b, hq, s, d), bf, one_chip),
                _sds((b, hkv, s, d), bf, one_chip),
                _sds((b, hkv, s, d), bf, one_chip)).compile()
    assert c.as_text().count(KERNEL) == 1


def test_matmul_pom_compiles_at_smollm_mlp_width(one_chip):
    from repro.kernels import ops
    bf = jnp.bfloat16
    f = jax.jit(lambda x, y: ops.matmul(x, y, impl="pallas",
                                        interpret=False))
    c = f.lower(_sds((4096, 960), bf, one_chip),
                _sds((960, 2560), bf, one_chip)).compile()
    assert c.as_text().count(KERNEL) == 1


def _smollm_shapes(monkeypatch):
    import repro.kernels.decode_attention as da
    from repro.configs.base import get_config
    from repro.models import init_cache, init_params
    monkeypatch.setattr(da, "pallas_interpret", lambda: False)
    cfg = dataclasses.replace(get_config("smollm_360m"), use_pallas=True)
    params = jax.eval_shape(lambda k: init_params(k, cfg), jax.random.key(0))
    cache = jax.eval_shape(lambda: init_cache(cfg, 4, 256))
    return cfg, params, cache


def _placed(tree, shardings):
    return jax.tree_util.tree_map(
        lambda x, s: _sds(x.shape, x.dtype, s), tree, shardings)


def _decode_step_compiled(cfg, params, cache, tok):
    from repro.models import decode_step
    return jax.jit(lambda p, c, t, q: decode_step(p, cfg, c, t, q)).lower(
        params, cache, tok, tok).compile()


def test_smollm_decode_step_compiles_with_the_kernel(one_chip, monkeypatch):
    """The whole 32-layer published-width decode step: one kernel in the
    scan over layers, and it fits the chip."""
    cfg, params, cache = _smollm_shapes(monkeypatch)
    on_chip = lambda t: jax.tree_util.tree_map(lambda _: one_chip, t)
    c = _decode_step_compiled(cfg, _placed(params, on_chip(params)),
                              _placed(cache, on_chip(cache)),
                              _sds((4,), jnp.int32, one_chip))
    assert c.as_text().count(KERNEL) == 1
    assert c.memory_analysis().temp_size_in_bytes < 2 ** 30


@pytest.fixture(scope="module")
def data_mesh(topo):
    return Mesh(np.array(topo.devices).reshape(4, 1), ("data", "model"))


def test_smollm_decode_step_on_a_4x1_mesh(data_mesh, monkeypatch):
    """The serve launcher's step on a 4x1 data mesh, placed by its
    ``step_shardings``: the decode kernel is split per device by its
    ``shard_map`` (GSPMD alone refuses a Mosaic kernel), and no
    collective moves the cache."""
    from repro.distributed.sharding import use_mesh
    from repro.launch.serve import step_shardings
    cfg, params, cache = _smollm_shapes(monkeypatch)
    with use_mesh(data_mesh) as mc:
        param_sh, cache_sh, row_sh = step_shardings(cfg, mc, params, cache, 4)
        c = _decode_step_compiled(cfg, _placed(params, param_sh),
                                  _placed(cache, cache_sh),
                                  _sds((4,), jnp.int32, row_sh))
    text = c.as_text()
    assert text.count(KERNEL) == 1
    assert "all-gather" not in text and "all-to-all" not in text
