"""Shared by the chip benchmark's tests: the cells of BENCHMARK.json at a
size a CPU test run holds, and the harness driven without its look for a
chip."""
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[3]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from benchmarks.chip import harness  # noqa: E402

SMALL_N = 32
SMALL_TILES = [8, 8, 16]
# traffic files that BENCHMARK.json has no cell for yet (see PERF.md,
# Open questions); the harness is tested on them all the same
PENDING = [
    {"name": "gemm_4096.dse", "config": "gemm_4096", "traffic": "dse",
     "chips": 1},
    {"name": "gaussian_4096.batched8", "config": "gaussian_4096",
     "traffic": "batched8", "chips": 1}]
CELLS = ["gemm_4096.tiled", "gaussian_4096.jitted", "gemm_4096.dse",
         "gaussian_4096.batched8"]


def small_cell(name: str) -> "harness.Cell":
    spec = harness.load_spec()
    known = {w["name"] for w in spec["workloads"]}
    spec["workloads"] += [w for w in PENDING if w["name"] not in known]
    cell = harness.resolve(spec, name)
    config = dict(cell.config, n=SMALL_N)
    if "tiled" in config["schedules"]:
        config["schedules"] = dict(config["schedules"],
                                   tiled={"tiles": SMALL_TILES})
    cell.config = config
    return cell


def run_small(name: str, seed: int = 2**33 + 5, seconds: float = 0.2,
              cell=None) -> dict:
    """One run on whatever devices JAX has (the CPU in tests)."""
    import jax
    return harness.run_cell(cell or small_cell(name), seed, seconds, False,
                            devices=jax.devices(),
                            peaks=harness.peaks_for("TPU v5 lite"), t0=0.0,
                            log=lambda _msg: None)
