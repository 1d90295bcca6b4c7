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
CELLS = ["gemm_4096.tiled", "gaussian_4096.jitted", "gemm_4096.dse",
         "gaussian_4096.batched8"]


def small_cell(name: str) -> "harness.Cell":
    cell = harness.resolve(harness.load_spec(), name)
    config = dict(cell.config, n=SMALL_N)
    if "tiled" in config["schedules"]:
        config["schedules"] = dict(config["schedules"],
                                   tiled={"tiles": SMALL_TILES})
    cell.config = config
    return cell


def run_small(name: str = "", seed: int = 2**33 + 5, seconds: float = 0.2,
              cell=None) -> dict:
    """One run on whatever devices JAX has (the CPU in tests)."""
    import jax
    return harness.run_cell(cell or small_cell(name), seed, seconds, False,
                            devices=jax.devices(),
                            peaks=harness.peaks_for("TPU v5 lite"), t0=0.0,
                            log=lambda _msg: None)


# the model-stack decode program of the ``build`` hook: test data, not a
# cell; a scratch copy of the spec names it (``lm_spec``)
LM_CONFIG = {"name": "smollm_360m_test",
             "source": "https://huggingface.co/HuggingFaceTB/SmolLM-360M/"
                       "blob/main/config.json",
             "file": "benchmarks/chip/tests/lm_decode_small.json",
             "reduced": [], "why": "the build hook's test program"}
LM_CELL = {"name": "smollm_360m_test.decode_64x2k",
           "config": "smollm_360m_test", "traffic": "decode_64x2k",
           "chips": 1, "why": "one decode step of 64 requests at context "
                              "2048, after a teacher-forced prefill"}


def lm_spec() -> dict:
    """BENCHMARK.json with the decode program's configuration and cell."""
    spec = harness.load_spec()
    spec["configs"].append(dict(LM_CONFIG))
    spec["workloads"].append(dict(LM_CELL))
    return spec


def small_lm_cell(dtype: str = "bfloat16", requests: int = 4,
                  context: int = 40) -> "harness.Cell":
    """The decode program at the widths of ``reduced(smollm_360m)`` with
    two KV heads (so attention is grouped), in ``dtype``."""
    from repro.configs.base import get_config, reduced
    cell = harness.resolve(lm_spec(), LM_CELL["name"])
    cfg = reduced(get_config("smollm_360m"), num_kv_heads=2)
    cell.config = dict(cell.config, dtype=dtype,
                       **{k: getattr(cfg, k) for k in cell.program.SIZES})
    cell.traffic = dict(cell.traffic, requests=requests, context=context)
    return cell


if __name__ == "__main__":
    # python3 benchmarks/chip/tests/chipbench_small.py DIR: write the
    # spec with the decode program's cell into DIR/BENCHMARK.json
    import json
    Path(sys.argv[1], "BENCHMARK.json").write_text(json.dumps(lm_spec()))
