"""Readings that the limits of ``check.py`` are set from, for one cell.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,... \\
        [--control-seeds 7,8,9] [--fault-seeds 7,8,9] [--out <file.json>]

In one process, at the cell's own size: for each of ``--seeds`` the program
is driven through its first three segments (one compiled program, reloaded
per seed) and compared with the plain reference; for each of
``--control-seeds`` the reference computed at each of ``CONTROLS``, the
precisions below float32 (``fedref._precise``), is compared in the
program's place; for each of ``--fault-seeds`` the reference with a
planted fault is: half of every minibatch left out, the exchange between
chips left out (clients-mesh cells), the parameters left unchanged. The benchmark's own runs do not run
this. It prints one line per reading and, at the end, per number the
largest sound reading and the smallest control and fault readings.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import run  # noqa: E402


CONTROLS = ("high", "bf16x3", "default", "bf16")


def _seeds(text):
    return [int(s) for s in text.split(",") if s]


def readings(workload, seeds, control_seeds, fault_seeds, *,
             require_chip=True, overrides=None):
    """{"program": {seed: values}, "control": {...}, "faults": {name:
    {seed: values}}} and the summary per number."""
    import check

    spec = run.resolve(workload)
    spec["fz"].update(overrides or {})
    if require_chip:
        run.check_chip(spec["cell"]["chips"],
                       json.loads((run.BENCH / "peaks.json").read_text()))
    channel = spec["fz"].get("channel") is not None
    out = {"program": {}, "control": {}, "faults": {}}
    prog = None
    for seed in seeds:
        inputs = run.make_inputs(spec, seed)
        p0 = run.host(inputs[3])
        if prog is None:
            prog = run.Program(spec, seed, inputs, require_chip=require_chip)
        else:
            prog.load(inputs)
        first = run.setup_steps(prog)
        ref = run.reference(spec, seed, inputs=inputs[:3]
                            + (p0, run.run_key(seed)))
        out["program"][seed] = check.compare(first, ref, p0, channel)
        run.log(f"program seed={seed} {out['program'][seed]}")
    if prog is not None:
        prog.free()
    faults = ["half_batch", "unchanged"]
    if spec["fz"].get("mesh_clients", 1) > 1:
        faults.append("no_exchange")
    for seed in sorted(set(control_seeds) | set(fault_seeds)):
        inputs = run.make_inputs(spec, seed)
        p0 = run.host(inputs[3])
        base = run.reference(spec, seed, inputs=inputs)
        if seed in control_seeds:
            for prec in CONTROLS:
                ctl = run.reference(spec, seed, precision=prec,
                                    inputs=inputs)
                out["control"].setdefault(prec, {})[seed] = check.compare(
                    ctl, base, p0, channel)
                run.log(f"control {prec} seed={seed} "
                        f"{out['control'][prec][seed]}")
        if seed in fault_seeds:
            for f in faults:
                bad = run.reference(spec, seed, fault=f, inputs=inputs)
                out["faults"].setdefault(f, {})[seed] = check.compare(
                    bad, base, p0, channel)
                run.log(f"fault {f} seed={seed} {out['faults'][f][seed]}")
    names = list(next(iter(out["program"].values())).keys()) \
        if out["program"] else []
    summary = {}
    for k in names:
        summary[k] = {
            "lower": max(v[k] for v in out["program"].values()),
            **{f"control.{p}": min(v[k] for v in cv.values())
               for p, cv in out["control"].items()},
            **{f"fault.{f}": min(v[k] for v in fv.values())
               for f, fv in out["faults"].items()}}
    return out, summary


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=_seeds, default=[])
    ap.add_argument("--control-seeds", type=_seeds, default=[])
    ap.add_argument("--fault-seeds", type=_seeds, default=[])
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    from repro.utils import compile_cache
    compile_cache.enable()
    try:
        out, summary = readings(args.workload, args.seeds,
                                args.control_seeds, args.fault_seeds)
    except run.NoChip as e:
        run.log(f"calibrate: {e}")
        return 2
    for k, v in summary.items():
        print(f"{k}: {json.dumps(v)}", flush=True)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps(
            {"workload": args.workload, "readings": out,
             "summary": summary}, indent=1, default=str))
    return 0


if __name__ == "__main__":
    sys.exit(main())
