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
import re

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


def test_dse_gemm_4096_step_has_no_gather_scatter_or_sort(one_chip):
    """The DSE splits ``i`` into ``32*i_o + i_u``: ``A``'s load and the
    accumulator store of ``C`` are digit windows, so the chip's compiler
    gets slices, relayouts and a dynamic-update-slice, no gather, scatter
    or sort, and the multiply-reduce keeps the nest's iteration shape."""
    prog = pcompile(workloads.gemm(4096).fn, target="pallas",
                    interpret=False, dse=True)
    text = _step_text(prog, one_chip)
    assert KERNEL not in text
    assert "f32[4096,128,32]" in text
    for op in ("gather", "scatter", "sort"):
        assert f" {op}(" not in text, op


SMOLLM = dict(b=4, hq=15, hkv=5, d=64, s=256)


def test_decode_attention_compiles_at_smollm_width(one_chip):
    from repro.kernels.decode_attention import decode_attention_rows
    b, hq, hkv, d, s = (SMOLLM[k] for k in ("b", "hq", "hkv", "d", "s"))
    bf = jnp.bfloat16
    f = jax.jit(lambda q, k, v, n: decode_attention_rows(
        q, k, v, length=n, bkv=128, interpret=False))
    c = f.lower(_sds((b, hq, d), bf, one_chip),
                _sds((b, s, hkv * d), bf, one_chip),
                _sds((b, s, hkv * d), bf, one_chip),
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


def test_ssm_scan_compiles_at_granite_width(one_chip):
    """The chunked scan at granite-4.0-h-micro's Mamba-2 widths (64 heads
    of 64, state 128, one group) over a 4096-token prefill, with the
    chunk POM's schedule picks."""
    from repro.kernels import ops
    import repro.kernels.ssm_scan as ss
    b, s, h, p, g, n = 1, 4096, 64, 64, 1, 128
    f32 = jnp.float32
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(ss, "pallas_interpret", lambda: False)
        f = jax.jit(lambda x, a, bb, c: ops.ssm_scan(x, a, bb, c,
                                                     impl="pallas"))
        c = f.lower(_sds((b, s, h, p), f32, one_chip),
                    _sds((b, s, h), f32, one_chip),
                    _sds((b, s, g, n), f32, one_chip),
                    _sds((b, s, g, n), f32, one_chip)).compile()
    assert c.as_text().count(KERNEL) == 1


@pytest.fixture(scope="module")
def granite_step(one_chip):
    """The published-width granite-4.0-h-micro decode step of 32 requests
    at context 4096, jitted as the benchmark's program jits it (K/V and
    the buffer the new SSM state goes to donated, the prefill's state
    read), compiled: (compiled, K/V and state shapes)."""
    import repro.kernels.decode_attention as da
    from repro.configs.base import get_config
    from repro.models import decode_step, init_cache, init_params
    cfg = dataclasses.replace(get_config("granite_4_0_h_micro"),
                              use_pallas=True)
    on = lambda t: jax.tree_util.tree_map(  # noqa: E731
        lambda x: _sds(x.shape, x.dtype, one_chip), t)
    params = on(jax.eval_shape(lambda k: init_params(k, cfg),
                               jax.random.key(0)))
    cache = on(jax.eval_shape(lambda: init_cache(cfg, 32, 4096)))
    kv = {n: c for n, c in cache.items() if "k" in c}
    state = {n: c for n, c in cache.items() if "k" not in c}

    def step(params, rw, state, token, pos):
        logits, new = decode_step(params, cfg, {**rw[0], **state}, token, pos)
        return logits, ({n: new[n] for n in rw[0]},
                        {n: new[n] for n in state})
    tok = _sds((32,), jnp.int32, one_chip)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(da, "pallas_interpret", lambda: False)
        c = jax.jit(step, donate_argnums=(1,), keep_unused=True).lower(
            params, (kv, state), state, tok, tok).compile()
    return c, kv, state


def test_granite_decode_step_writes_its_cache_in_place(granite_step):
    """The granite step: one kernel, every donated byte aliased to an
    output, no copy of a cache (the K/V rows and the state are laid out
    as the chip keeps them), and it fits the chip."""
    c, kv, state = granite_step
    text = c.as_text()
    assert text.count(KERNEL) == 1
    mem = c.memory_analysis()
    donated = sum(x.size * x.dtype.itemsize
                  for x in jax.tree_util.tree_leaves((kv, state)))
    assert mem.alias_size_in_bytes == donated
    # a layer's K/V or SSM state is 67 MB or more; no copy comes near it
    item = {"f32": 4, "bf16": 2, "s32": 4}
    for m in re.finditer(r"= (f32|bf16|s32)\[([\d,]*)\]\S* copy\(", text):
        size = item[m.group(1)] * int(np.prod([int(d) for d in
                                               m.group(2).split(",") if d]))
        assert size < 2 ** 24, m.group(0)
    assert mem.temp_size_in_bytes < 2 ** 28
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 13.5e9


def test_granite_state_updates_carry_the_mixer_scope(granite_step):
    """Each Mamba layer's state update is one fusion whose root is the
    layer scan's write; the mixer's ops fused into it carry
    ``mamba2_decode`` in their op_name, so a reader of the trace can
    find the state update by the computation each device op calls."""
    from repro.models.mamba2 import DECODE_SCOPE
    text = granite_step[0].as_text()
    calls = re.findall(r"= f32\[4,32,64,64,128\]\S* fusion\(.*?calls=(%[\w.]+)",
                       text)
    # the nine Mamba positions of the period, each over the four repeats
    assert len(calls) == 9
    bodies = {m.group(1): m.group(2) for m in re.finditer(
        r"\n(%[\w.]+) [^\n]*\{\n(.*?)\n\}", text, re.S)}
    for name in calls:
        assert f"/{DECODE_SCOPE}/" in bodies[name], name
