"""Multi-device distributed checks, run in a subprocess with 8 fake devices.

Each check prints 'OK <name>' on success; the pytest wrapper asserts on it.
Invoked as:  python tests/helpers/dist_checks.py <check_name>
"""
import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=8")
import sys

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "..", "src"))


def _mesh22():
    devs = np.array(jax.devices()[:8]).reshape(2, 2, 2)
    return Mesh(devs, ("pod", "data", "model"))


def check_train_step_sharded():
    """One real sharded train step on a reduced arch: loss decreases."""
    from repro.configs.base import ParallelConfig, get_config, reduced
    from repro.distributed import step as step_mod
    from repro.distributed.sharding import use_mesh, current
    from repro.models import init_params
    from repro.optim import adamw_init
    from repro.data import SyntheticLM, make_device_batch
    from repro.configs.base import ShapeConfig

    cfg = reduced(get_config("smollm_360m"), d_model=64, num_layers=2,
                  num_heads=4, num_kv_heads=2, head_dim=16, d_ff=128,
                  vocab_size=256)
    shape = ShapeConfig("t", 32, 8, "train")
    mesh = _mesh22()
    with use_mesh(mesh):
        mc = current()
        jitted, (param_sh, opt_sh, batch_sh) = step_mod.make_train_step(
            cfg, ParallelConfig(), mc, peak_lr=1e-2, warmup=5)
        params = jax.jit(lambda k: init_params(k, cfg),
                         out_shardings=param_sh)(jax.random.key(0))
        opt = adamw_init(params)
        ds = SyntheticLM(cfg, shape, seed=1)
        losses = []
        for i in range(40):
            batch = make_device_batch(ds.batch_at(i), batch_sh)
            params, opt, metrics = jitted(params, opt, batch)
            losses.append(float(metrics["loss"]))
        assert np.isfinite(losses).all(), losses
        assert min(losses[-5:]) < losses[0] - 0.3, f"no learning: {losses}"
    print("OK check_train_step_sharded")


def check_compressed_psum():
    """int8+EF compressed all-reduce ~ exact psum; EF shrinks the error."""
    from repro.distributed.compression import compressed_psum

    devs = np.array(jax.devices()[:8])
    mesh = Mesh(devs, ("data",))
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(8, 64, 33)), jnp.float32)
    r0 = jnp.zeros((64, 33), jnp.float32)

    def f(xs, rs):
        g, r = compressed_psum(xs[0], rs[0], "data")
        return g[None], r[None]

    fm = jax.shard_map(f, mesh=mesh, in_specs=(P("data"), P("data")),
                       out_specs=(P("data"), P("data")))
    got, resid = fm(x, jnp.tile(r0[None], (8, 1, 1)))
    want = jnp.sum(x, axis=0)
    err = float(jnp.max(jnp.abs(got[0] - want)) / (jnp.max(jnp.abs(want)) + 1e-9))
    assert err < 0.05, f"compressed allreduce error {err}"
    # every replica computed the same sum
    assert np.allclose(np.asarray(got[0]), np.asarray(got[3]))
    # error feedback: residual captures exactly the quantization error
    assert float(jnp.max(jnp.abs(resid))) > 0.0
    print("OK check_compressed_psum")


def check_elastic_reshard():
    """Checkpoint saved on a 2x4 mesh restores onto a 4x2 and 1x8 mesh."""
    import tempfile
    from repro.checkpoint import save_pytree, restore_pytree

    devs = np.array(jax.devices()[:8])
    mesh_a = Mesh(devs.reshape(2, 4), ("data", "model"))
    mesh_b = Mesh(devs.reshape(4, 2), ("data", "model"))
    tree = {"w": jnp.arange(64, dtype=jnp.float32).reshape(8, 8),
            "b": jnp.ones((8,), jnp.float32)}
    sh_a = {"w": NamedSharding(mesh_a, P("data", "model")),
            "b": NamedSharding(mesh_a, P("model"))}
    placed = jax.tree_util.tree_map(jax.device_put, tree, sh_a)
    with tempfile.TemporaryDirectory() as d:
        save_pytree(placed, d, step=7)
        sh_b = {"w": NamedSharding(mesh_b, P("data", "model")),
                "b": NamedSharding(mesh_b, P("data"))}
        restored, step = restore_pytree(tree, d, shardings=sh_b)
        assert step == 7
        np.testing.assert_array_equal(np.asarray(restored["w"]),
                                      np.asarray(tree["w"]))
        assert restored["w"].sharding == sh_b["w"]
    print("OK check_elastic_reshard")


def check_decode_sp_longcontext():
    """Sequence-sharded KV decode == replicated decode (flash-decode SP)."""
    from repro.kernels import ref
    devs = np.array(jax.devices()[:8])
    mesh = Mesh(devs, ("model",))
    b, hq, hkv, s, d = 2, 4, 2, 64, 16
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.normal(size=(b, hq, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, hkv, s, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, hkv, s, d)), jnp.float32)
    want = ref.decode_attention(q, k, v)
    ksh = jax.device_put(k, NamedSharding(mesh, P(None, None, "model", None)))
    vsh = jax.device_put(v, NamedSharding(mesh, P(None, None, "model", None)))
    with mesh:
        got = jax.jit(ref.decode_attention)(q, ksh, vsh)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5,
                               atol=1e-5)
    print("OK check_decode_sp_longcontext")


def check_serve_mesh():
    """The serve loop on data, model and 2-D meshes == one device: the
    same logits (f32) and greedy tokens, the decode kernel shard_map'd."""
    import dataclasses
    from repro.configs.base import get_config, reduced
    from repro.launch.mesh import make_mesh
    from repro.launch.serve import serve
    from repro.models import init_params
    cfg = dataclasses.replace(reduced(get_config("smollm_360m")),
                              use_pallas=True)
    params = init_params(jax.random.key(0), cfg)
    prompts = jnp.asarray(np.random.default_rng(0).integers(
        0, cfg.vocab_size, (4, 6)), jnp.int32)
    one = serve(cfg, params, prompts, 4)
    for shape in ((4, 1), (1, 2), (2, 2)):
        got = serve(cfg, params, prompts, 4,
                    mesh=make_mesh(shape, ("data", "model")))
        np.testing.assert_allclose(np.asarray(got.logits),
                                   np.asarray(one.logits),
                                   rtol=1e-5, atol=1e-5, err_msg=str(shape))
        np.testing.assert_array_equal(np.asarray(got.tokens),
                                      np.asarray(one.tokens))
    print("OK check_serve_mesh")


def check_pp_gpipe():
    """GPipe pipeline forward == sequential forward on a toy MLP stack."""
    from repro.distributed.pp import gpipe_forward
    devs = np.array(jax.devices()[:8])
    mesh = Mesh(devs.reshape(4, 2), ("stage", "data"))
    nstage, nlayer, d = 4, 8, 16
    rng = np.random.default_rng(2)
    ws = jnp.asarray(rng.normal(size=(nlayer, d, d)) / np.sqrt(d), jnp.float32)
    x = jnp.asarray(rng.normal(size=(8, 4, d)), jnp.float32)  # (mb, b, d)

    def layer(w, h):
        return jnp.tanh(h @ w)

    want = x
    for i in range(nlayer):
        want = layer(ws[i], want)

    got = gpipe_forward(layer, ws, x, mesh, stage_axis="stage",
                        n_microbatches=8)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5,
                               atol=1e-5)
    print("OK check_pp_gpipe")


def check_dryrun_small_mesh():
    """run_cell logic on a small mesh: lower-only for one arch/shape."""
    from repro.configs.base import SHAPES, ParallelConfig, get_config, reduced
    from repro.distributed import step as step_mod
    from repro.distributed.sharding import use_mesh, current
    from repro.models import init_params
    cfg = reduced(get_config("granite_moe_1b"), vocab_size=256)
    mesh = _mesh22()
    from repro.configs.base import ShapeConfig
    shape = ShapeConfig("t", 64, 8, "train")
    with use_mesh(mesh):
        mc = current()
        jitted, _ = step_mod.make_train_step(cfg, ParallelConfig(), mc)
        params_shapes = jax.eval_shape(
            lambda k: init_params(k, cfg),
            jax.ShapeDtypeStruct((), jax.random.key(0).dtype))
        from repro.optim import adamw_init
        opt_shapes = jax.eval_shape(lambda p: adamw_init(p), params_shapes)
        batch = step_mod.input_specs(cfg, shape)
        compiled = jitted.lower(params_shapes, opt_shapes, batch).compile()
        assert compiled.cost_analysis() is not None
    print("OK check_dryrun_small_mesh")


if __name__ == "__main__":
    globals()[sys.argv[1]]()
