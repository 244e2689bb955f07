"""Record a small chip trace of one cell as test data for
``bench/tests/test_scope_reduce.py``.

    python3 bench/record_trace.py --workload softmax_fmnist.aircomp_fading \
        --seed 7 --out bench/testdata/aircomp_2seg

The cell's program is compiled at one round a segment, run once to warm
it, then two segments run under the profiler exactly as ``run.py``'s
traced window runs them. Writes ``<out>.xplane.pb`` and prints its busy
time and its split by the program's named scopes (``scope_reduce``).
"""
from __future__ import annotations

import argparse
import json
import shutil
import sys
import tempfile
from pathlib import Path

import run
import scope_reduce
import trace_reduce


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    import jax

    spec = run.resolve(args.workload)
    spec["fz"]["segment_rounds"] = 1
    run.check_chip(spec["cell"]["chips"], run._read(run.BENCH / "peaks.json"))
    prog = run.Program(spec, args.seed, run.make_inputs(spec, args.seed))
    jax.block_until_ready(prog.segment())
    out = f"{args.out}.xplane.pb"
    with tempfile.TemporaryDirectory() as tmp:
        path, _ = run.traced_window(prog, 2, Path(tmp))
        shutil.copyfile(path, out)
    red = trace_reduce.reduce(out)
    devices, host = trace_reduce.read_trace(out)
    split = scope_reduce.scope_seconds(devices, host,
                                       scope_reduce.op_paths(out),
                                       scope_reduce.program_scopes())
    print(json.dumps({"window_s": red["window_s"], "busy_s": red["busy_s"],
                      "scope_s": split}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
