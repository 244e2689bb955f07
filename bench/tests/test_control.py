"""The comparison that decides ``correct`` fails what it must.

- The control: the plain reference with its matmuls and convolutions in
  one bfloat16 pass (operands rounded to bfloat16, float32 accumulation;
  what a TPU computes for float32 at its default precision), put in the
  program's place, fails at least one of the cell's numbers against the
  cell's limits. The CPU ignores the ``precision`` of a dot, so the pass
  is spelled out (``fedref`` precision ``"bf16"``). The three-pass control
  (``"bf16x3"``) is read on the chip by ``calibrate.py``: at this size its
  error on the loss is under one float32 ulp.
- The harness, its chip check skipped, driving the program with a fault
  planted under it, reports ``correct`` false: a round that returns its
  state unchanged; half of every minibatch left out; on a clients mesh,
  the exchange between chips left out.

Both at a size the CPU holds (``tiny.py``); ``calibrate.py`` reads the same
on the chip at each cell's own size.

    python -m pytest bench/tests
"""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH / "tests"))

import run  # noqa: E402
import tiny  # noqa: E402

BM = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BM["workloads"]]
CONTROL_SEEDS = (11, 12, 13)


def _faults(workload):
    spec = run.resolve(workload)
    out = ["unchanged", "half_batch"]
    if spec["fz"].get("mesh_clients", 1) > 1:
        out.append("no_exchange")
    return out


@pytest.mark.parametrize("workload", CELLS)
def test_control_fails(workload):
    import check

    spec = run.resolve(workload)
    spec["fz"].update(tiny.overrides(workload))
    channel = spec["fz"].get("channel") is not None
    for seed in CONTROL_SEEDS:
        inputs = run.make_inputs(spec, seed)
        p0 = run.host(inputs[3])
        base = run.reference(spec, seed, inputs=inputs)
        ctl = run.reference(spec, seed, precision="bf16", inputs=inputs)
        ok, checks = check.judge(check.compare(ctl, base, p0, channel),
                                 spec["limits"])
        assert not ok, (seed, checks)


def _on_tpu():
    import jax
    return jax.devices()[0].platform == "tpu"


@pytest.mark.parametrize("workload", CELLS)
def test_high_control_fails_on_tpu(workload):
    """The reference at the TPU's ``high`` precision (three bfloat16 passes)
    in the program's place fails the round-0 first-iterate loss: at the
    cell's own widths, first round only, which is all that number reads.
    Needs a TPU: the CPU computes every float32 dot in full."""
    if not _on_tpu():
        pytest.skip("needs a TPU: the CPU ignores a dot's precision")
    import check

    spec = run.resolve(workload)
    spec["fz"]["segment_rounds"] = 1
    channel = spec["fz"].get("channel") is not None
    for seed in CONTROL_SEEDS:
        inputs = run.make_inputs(spec, seed)
        p0 = run.host(inputs[3])
        base = run.reference(spec, seed, inputs=inputs)
        ctl = run.reference(spec, seed, precision="high", inputs=inputs)
        gap = check.compare(ctl, base, p0, channel)["first_loss0_gap"]
        assert gap > spec["limits"]["first_loss0_gap"], (seed, gap)


@pytest.mark.parametrize("workload,fault", [(w, f) for w in CELLS
                                            for f in _faults(w)])
def test_planted_fault_is_not_correct(workload, fault):
    out = subprocess.run(
        [sys.executable, str(BENCH / "tests" / "tiny.py"), workload, fault],
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, capture_output=True,
        text=True, timeout=600)
    lines = [l for l in out.stdout.splitlines() if l.startswith("{")]
    assert lines, out.stderr[-3000:]
    res = json.loads(lines[-1])
    assert res["correct"] is False, res["checks"]
