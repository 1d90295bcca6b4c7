"""BENCHMARK.json and the files it names; ``work()``, the peaks table and
the command's refusals."""
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from chipbench_small import ROOT

from benchmarks.chip import harness

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SPEC = harness.load_spec()


def test_names_units_and_files():
    chip = ROOT / "benchmarks" / "chip"
    names = [c["name"] for c in SPEC["configs"]]
    names += [w["name"] for w in SPEC["workloads"]]
    metrics = SPEC["end_to_end"] + SPEC["per_layer"]
    names += [m["name"] for m in metrics]
    assert all(NAME.match(n) for n in names), names
    assert len(set(m["name"] for m in metrics)) == len(metrics)
    assert all(UNIT.match(m["unit"]) for m in metrics)
    assert all(m["better"] in ("lower", "higher") for m in metrics)
    for c in SPEC["configs"]:
        assert c["file"].startswith("benchmarks/chip/")
        config = json.loads((ROOT / c["file"]).read_text())
        assert (chip / "configs" / f"{config['program']}.py").is_file()
        assert config["limits"]["max_rel_err"] > 0
    pairs = [(w["config"], w["traffic"]) for w in SPEC["workloads"]]
    assert len(set(pairs)) == len(pairs)
    for w in SPEC["workloads"]:
        assert (chip / "traffic" / f"{w['traffic']}.json").is_file()
        assert w["chips"] == 1 and len(w["why"]) <= 200
    for m in SPEC["per_layer"]:
        assert (chip / "metrics" / f"{m['name']}.py").is_file()
        assert m["moves"] in {e["name"] for e in SPEC["end_to_end"]}
    for m in SPEC["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25


def test_every_cell_reports_setup_another_end_to_end_and_a_layer():
    for w in SPEC["workloads"]:
        cell = harness.resolve(SPEC, w["name"])
        e2e = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in e2e and len(e2e) >= 2
        assert cell.per_layer


def test_work_of_both_configurations():
    gemm = harness.resolve(SPEC, "gemm_4096.tiled")
    w = gemm.program.work(gemm.config, gemm.traffic)
    assert w["total"] == {"flops": 2 * 4096 ** 3, "bytes": 16 * 4096 ** 2}
    assert w["contraction"] == w["total"]
    gauss = harness.resolve(SPEC, "gaussian_4096.jitted")
    w = gauss.program.work(gauss.config, gauss.traffic)
    assert w["total"] == {"flops": 14 * 4094 ** 2,
                          "bytes": 4 * 4096 ** 2 + 4 * 4094 ** 2}
    assert "contraction" not in w


def test_rooflines_on_the_v5e():
    peaks = harness.peaks_for("TPU v5 lite")
    gemm = harness.resolve(SPEC, "gemm_4096.tiled")
    w = gemm.program.work(gemm.config, gemm.traffic)
    t = harness.roofline_s(w["total"], peaks)
    assert t == pytest.approx(2 * 4096 ** 3 / 197e12)        # compute-bound
    gauss = harness.resolve(SPEC, "gaussian_4096.jitted")
    w = gauss.program.work(gauss.config, gauss.traffic)
    t = harness.roofline_s(w["total"], peaks, lanes=8)
    assert t == pytest.approx(8 * 134152208 / 819e9)          # bytes-bound


def test_peaks_refuse_an_unknown_device_kind():
    assert harness.peaks_for("TPU v5 lite")["flops_per_s"] == 197e12
    with pytest.raises(KeyError, match="TPU v4"):
        harness.peaks_for("TPU v4")
    with pytest.raises(KeyError):
        harness.peaks_for("cpu")


def _run(cwd, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "gemm_4096.tiled", "--seed", "1", "--seconds", "1", "--trace", "0",
         *args], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def test_no_tpu_exits_nonzero_with_no_result():
    p = _run(ROOT)
    assert p.returncode == 2, p.stderr
    assert "TPU" in p.stderr
    assert "{" not in p.stdout


def test_benchmark_files_alone_exit_nonzero_with_no_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "benchmarks" / "chip",
                    tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns(".jax_cache", ".out",
                                                  "__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert "{" not in p.stdout
