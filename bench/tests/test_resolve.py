"""Every cell of BENCHMARK.json resolves to its files by name and runs
through the harness at a tiny size on the CPU; a machine without a TPU, or
a checkout without the program, gets no result line.

    python -m pytest bench/tests
"""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

import run  # noqa: E402

BM = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BM["workloads"]]
CPU = {**os.environ, "JAX_PLATFORMS": "cpu"}


def _last_json(stdout):
    lines = [l for l in stdout.splitlines() if l.startswith("{")]
    return json.loads(lines[-1]) if lines else None


@pytest.mark.parametrize("workload", CELLS)
def test_cell_resolves_by_name(workload):
    spec = run.resolve(workload)
    cfg = spec["config"]
    for key in ("reduced", "assumed", "source", "d"):
        assert key in cfg, key
    for fn in ("init_params", "program_model", "ref_logits",
               "flops_per_sample"):
        assert callable(getattr(spec["model"], fn)), fn
    assert spec["limits"], f"bench/limits/{workload}.json has no limits"
    for name, path in spec["metric_readers"].items():
        assert path.exists(), name
        assert callable(run.load_module(path, "m_" + name.replace(".", "_"))
                        .read)
    names = {m["name"] for m in spec["end_to_end"]}
    assert {"setup_s", "rounds_per_s"} <= names
    assert all(m["moves"] in names for m in spec["per_layer"])


@pytest.mark.parametrize("workload", CELLS)
def test_cell_runs_at_tiny_size_and_is_correct(workload):
    out = subprocess.run([sys.executable, str(BENCH / "tests" / "tiny.py"),
                          workload], env=CPU, capture_output=True, text=True,
                         timeout=600)
    res = _last_json(out.stdout)
    assert res is not None, out.stderr[-3000:]
    assert set(res) >= {"correct", "attempted", "failed", "metrics",
                        "device", "checks"}
    assert res["failed"] == 0
    assert res["correct"], res["checks"]
    assert {m["name"] for m in BM["end_to_end"]
            if workload in m.get("workloads", [workload])} \
        == set(res["metrics"])
    assert list(res)[-1] == "checks"


def test_mix_sets_only_keys_the_harness_knows():
    config = {"lr": 1e-3, "n_clients": 50}
    assert run.mix_overrides({"lr": 2e-3, "aircomp": True}, config) == {
        "lr": 2e-3, "aircomp": True}
    with pytest.raises(SystemExit, match="strategy"):
        run.mix_overrides({"strategy": "fedavg"}, config)


def test_no_tpu_no_result():
    out = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        env=CPU, capture_output=True, text=True, timeout=300, cwd=ROOT)
    assert out.returncode != 0
    assert _last_json(out.stdout) is None
    assert "no TPU" in out.stderr


def test_benchmark_files_alone_give_no_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    out = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0], "--seed",
         "1", "--seconds", "1", "--trace", "0"],
        env={**CPU, "PYTHONPATH": ""}, capture_output=True, text=True,
        timeout=300, cwd=tmp_path)
    assert out.returncode != 0
    assert _last_json(out.stdout) is None
