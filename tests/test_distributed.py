"""Distributed runtime tests.

Multi-device checks run in a subprocess with 8 fake CPU devices (the XLA
device-count flag must be set before jax initializes, so they cannot run in
the main pytest process which other tests need at 1 device).
"""
import os
import subprocess
import sys

import numpy as np
import pytest

HELPER = os.path.join(os.path.dirname(__file__), "helpers", "dist_checks.py")


def _run(check: str, timeout=600):
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, HELPER, check], capture_output=True,
                       text=True, timeout=timeout, env=env)
    assert r.returncode == 0, f"{check} failed:\n{r.stdout}\n{r.stderr}"
    assert f"OK {check}" in r.stdout


def test_train_step_sharded_learns():
    _run("check_train_step_sharded")


def test_compressed_psum_int8_ef():
    _run("check_compressed_psum")


def test_elastic_checkpoint_reshard():
    _run("check_elastic_reshard")


def test_decode_sp_long_context():
    _run("check_decode_sp_longcontext")


def test_serve_on_meshes_matches_one_device():
    _run("check_serve_mesh")


def test_pp_gpipe_forward():
    _run("check_pp_gpipe")


def test_dryrun_small_mesh_moe():
    _run("check_dryrun_small_mesh")


# ---------------------------------------------------------------------------
# single-process pieces (no mesh needed)
# ---------------------------------------------------------------------------
def test_quantize_roundtrip_error_bound():
    import jax.numpy as jnp
    from repro.distributed.compression import dequantize_int8, quantize_int8
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(1000,)), jnp.float32)
    q, s, n = quantize_int8(x)
    back = dequantize_int8(q, s, n, x.shape)
    err = float(jnp.max(jnp.abs(back - x)))
    assert err <= float(jnp.max(jnp.abs(x))) / 127.0 + 1e-6


def test_checkpoint_manager_roundtrip(tmp_path):
    import jax.numpy as jnp
    from repro.checkpoint import CheckpointManager
    mgr = CheckpointManager(str(tmp_path), keep=2)
    tree = {"a": jnp.arange(10.0), "b": {"c": jnp.ones((3, 3))}}
    for step in (1, 2, 3):
        mgr.save(tree, step)
    mgr.wait()
    # retention: only last 2 kept
    kept = sorted(d for d in os.listdir(tmp_path) if d.startswith("step_"))
    assert kept == ["step_2", "step_3"]
    restored, step = mgr.restore(tree)
    assert step == 3
    np.testing.assert_array_equal(np.asarray(restored["a"]),
                                  np.asarray(tree["a"]))


def test_checkpoint_detects_corruption(tmp_path):
    import jax.numpy as jnp
    from repro.checkpoint import restore_pytree, save_pytree
    tree = {"a": jnp.arange(4.0)}
    save_pytree(tree, str(tmp_path), step=1)
    # corrupt the payload
    path = tmp_path / "step_1" / "arrays.npz"
    data = path.read_bytes()
    path.write_bytes(data[:-4] + b"dead")
    with pytest.raises(IOError, match="digest"):
        restore_pytree(tree, str(tmp_path))


def test_heartbeat_straggler_detection(tmp_path):
    from repro.distributed.ft import Heartbeat, check_workers
    t0 = 1000.0
    for host in range(4):
        Heartbeat(str(tmp_path), host).beat(step=10, now=t0)
    # host 3 stalls: last beat long ago and behind on steps
    Heartbeat(str(tmp_path), 3).beat(step=5, now=t0 - 40)
    statuses = {w.host: w.state for w in
                check_workers(str(tmp_path), dead_after_s=60, now=t0)}
    assert statuses[0] == "healthy"
    assert statuses[3] == "straggler"
    # much later: host 3 dead
    statuses = {w.host: w.state for w in
                check_workers(str(tmp_path), dead_after_s=60, now=t0 + 30)}
    assert statuses[3] == "dead"
    assert statuses[0] == "healthy"


def test_plan_remesh_elastic():
    from repro.distributed.ft import plan_remesh
    assert plan_remesh(64, 4, 16) == (16, 16)       # full pod
    assert plan_remesh(60, 4, 16) == (8, 16)        # lost hosts -> shrink DP
    assert plan_remesh(3, 4, 16) == None            # cannot even fit TP
    assert plan_remesh(8, 4, 16) == (2, 16)


def test_data_pipeline_deterministic_resume():
    from repro.configs.base import ShapeConfig, get_config, reduced
    from repro.data import SyntheticLM
    cfg = reduced(get_config("smollm_360m"))
    ds = SyntheticLM(cfg, ShapeConfig("t", 16, 4, "train"), seed=7)
    b5 = ds.batch_at(5)
    ds2 = SyntheticLM(cfg, ShapeConfig("t", 16, 4, "train"), seed=7)
    b5b = ds2.batch_at(5)
    np.testing.assert_array_equal(b5["tokens"], b5b["tokens"])
    # iterator from step 5 yields batch 5 first (exact resume)
    it = ds.iter_from(5)
    first = next(iter(it))
    np.testing.assert_array_equal(first["tokens"], b5["tokens"])


def test_synthetic_data_is_learnable():
    """Labels are mostly a deterministic function of the prefix."""
    from repro.configs.base import ShapeConfig, get_config, reduced
    from repro.data import SyntheticLM
    cfg = reduced(get_config("smollm_360m"))
    ds = SyntheticLM(cfg, ShapeConfig("t", 64, 8, "train"), seed=0)
    b = ds.batch_at(0)
    toks, labels = b["tokens"], b["labels"]
    # stride recoverable: label[t] - token[t] == const for most positions
    d = (labels - toks) % cfg.vocab_size
    match = (d == np.median(d, axis=1, keepdims=True)).mean()
    assert match > 0.8
