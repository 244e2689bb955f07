"""On-chip benchmark of the FedZO engine, one cell per process.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

A cell is an entry of ``workloads`` in ``BENCHMARK.json``: a configuration
(``bench/configs/<config>.json`` with its model code beside it in
``<config>.py``) under a traffic mix (``bench/mixes/<traffic>.json``). The
harness finds every piece by name, so a new cell, mix or per-layer metric
(``bench/metrics/<metric>.py``) is new files plus an entry.

One run: refuse anything but a TPU with the chips the cell asks for; make
the federation's data and starting weights on the device from the seed;
compile the engine's experiment program (``sim.make_experiment_fn``: one
scan of ``segment_rounds`` rounds, carry donated and fed back); drive it
through its first three segments, which the plain reference
(``fedref.py``) later follows. All of that is set-up (``setup_s``). Then
segments run back to back until ``--seconds`` have passed, each timed on
the host to ``block_until_ready``. With ``--trace 1`` a window of about
``TRACE_SECONDS`` is traced instead and reduced to the per-layer metrics.
After the window the program's state is freed, the reference runs, and
``check.py`` decides ``correct``. The last stdout line is the result as
JSON; the numbers compared are the last stderr lines.
"""
from __future__ import annotations

import time

_T0 = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))

STEPS = 3            # segments the reference follows
TRACE_SECONDS = 2.0  # length of the traced window with --trace 1


class NoChip(RuntimeError):
    """The machine lacks the accelerator the cell needs."""


def log(msg):
    print(msg, file=sys.stderr, flush=True)


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _read(path: Path):
    return json.loads(path.read_text())


def resolve(workload: str, root: Path = ROOT) -> dict:
    """Everything a cell is made of, found by name from BENCHMARK.json."""
    bm = _read(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bm["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; known: "
                         f"{sorted(cells)}")
    cell = cells[workload]
    entry = {c["name"]: c for c in bm["configs"]}[cell["config"]]
    cfg_path = root / entry["file"]
    config = _read(cfg_path)
    model = load_module(cfg_path.with_suffix(".py"),
                        f"bench_config_{cell['config']}")
    mix = _read(root / "bench" / "mixes" / f"{cell['traffic']}.json")
    lim_path = root / "bench" / "limits" / f"{workload}.json"
    limits = _read(lim_path)["limits"] if lim_path.exists() else {}

    def applies(m):
        return workload in m.get("workloads", [workload])

    fz = {**config, **mix_overrides(mix, config)}
    return {"cell": cell, "config": config, "model": model, "fz": fz,
            "limits": limits,
            "end_to_end": [m for m in bm["end_to_end"] if applies(m)],
            "per_layer": [m for m in bm["per_layer"] if applies(m)],
            "metric_readers": {m["name"]: root / "bench" / "metrics"
                               / f"{m['name']}.py"
                               for m in bm["per_layer"] if applies(m)}}


# what a mix may set besides the configuration's own keys
MIX_KEYS = ("aircomp", "snr_db", "h_min", "channel_schedule", "channel",
            "mesh_clients")


def mix_overrides(mix: dict, config: dict) -> dict:
    """The keys a traffic mix sets over its configuration: any key of the
    configuration, or one of ``MIX_KEYS``. A channel is given by its
    Doppler product fd*T and battery, from which the AR(1) coefficient
    follows. Any other key is refused, so a mix never asks for something
    the harness would silently not do."""
    unknown = sorted(set(mix) - set(config) - set(MIX_KEYS))
    if unknown:
        raise SystemExit(f"traffic mix sets keys the harness does not know: "
                         f"{unknown}")
    out = dict(mix)
    if mix.get("channel") is not None:
        ch = mix["channel"]
        out["channel"] = {"rho": math.exp(-2.0 * math.pi * ch["doppler_fd_T"]),
                          "doppler_fd_T": ch["doppler_fd_T"],
                          "battery": ch.get("battery", 0.0)}
    return out


def check_precision(fz: dict):
    """The program's float32 matmul precision is the one the configuration
    states; a program that departs from it is refused."""
    import jax
    have = str(jax.config.jax_default_matmul_precision).lower()
    if have != fz["precision"]:
        raise RuntimeError(f"the program computes float32 products at "
                           f"{have!r}; the configuration states "
                           f"{fz['precision']!r}")


def check_chip(chips: int, peaks: dict):
    """The devices JAX sees, or NoChip: anything but a TPU with ``chips``
    devices of a kind in peaks.json is refused."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX platform is {devs[0].platform!r}")
    if len(devs) < chips:
        raise NoChip(f"cell needs {chips} chips, JAX sees {len(devs)}")
    if devs[0].device_kind not in peaks["kinds"]:
        raise NoChip(f"device kind {devs[0].device_kind!r} is not in "
                     f"bench/peaks.json")
    return devs


def seed_key(seed: int):
    import jax
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32) if seed >> 32 else key


def make_inputs(spec: dict, seed: int):
    """Data, starting weights and the round-0 key of the run, all from the
    seed: (clients, sizes, test_eval, params0, run_key)."""
    import jax
    import jax.numpy as jnp

    from data import make_federation

    fz = spec["fz"]
    key = seed_key(seed)
    img = fz.get("image_shape")
    clients, test = make_federation(
        jax.random.fold_in(key, 1), jnp.float32(fz["data_scale"]),
        n_train=fz["n_train"], n_test=fz["n_test"],
        n_features=fz["n_features"], n_classes=fz["n_classes"],
        n_clients=fz["n_clients"], image_shape=tuple(img) if img else None)
    rows = fz["eval_rows"]
    test_eval = jax.tree.map(lambda a: a[:rows], test)
    sizes = jnp.full((fz["n_clients"],), fz["n_train"] // fz["n_clients"],
                     jnp.int32)
    params0 = jax.jit(lambda k: spec["model"].init_params(k, fz))(
        jax.random.fold_in(key, 2))
    return clients, sizes, test_eval, params0, run_key(seed)


def run_key(seed: int):
    """Round-0 key of the experiment's key chain."""
    import jax
    return jax.random.fold_in(seed_key(seed), 3)


def fedzo_config(fz: dict, seed: int):
    from repro import sim
    from repro.configs.base import FedZOConfig

    ch = fz.get("channel")
    return FedZOConfig(
        n_devices=fz["n_clients"], n_participating=fz["n_participating"],
        local_iters=fz["local_iters"], lr=fz["lr"], mu=fz["mu"], b1=fz["b1"],
        b2=fz["b2"], weight_by_size=fz["weight_by_size"],
        flat_params=fz["flat_params"], direction_conv=fz["direction_conv"],
        seed=seed, aircomp=fz.get("aircomp", False),
        snr_db=fz.get("snr_db", 0.0), h_min=fz.get("h_min", 0.8),
        channel_schedule=fz.get("channel_schedule", False),
        channel_model=(sim.ChannelModel.from_doppler(
            ch["doppler_fd_T"], battery=ch["battery"]) if ch else None))


class Program:
    """The system under test for one cell: the compiled experiment program
    and its carry. One object is built, driven through the set-up segments
    and handed to the window."""

    def __init__(self, spec: dict, seed: int, inputs, *, require_chip=True):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec

        from repro import sim

        fz = spec["fz"]
        self.fz = fz
        self.seg = fz["segment_rounds"]
        clients, sizes, test_eval, params0, run_key = inputs
        cfg = fedzo_config(fz, seed)
        loss, acc = spec["model"].program_model(fz)

        def eval_fn(p):
            return {"test_acc": acc(p, test_eval),
                    "test_loss": loss(p, test_eval)}

        chips = fz.get("mesh_clients", 1)
        round_fn = None
        self.replicated = None
        if chips > 1:
            mesh = sim.make_clients_mesh(chips)
            round_fn = sim.make_sharded_round(loss, cfg, mesh)
            self.replicated = NamedSharding(mesh, PartitionSpec())
        self.devices = (list(mesh.devices.flat) if chips > 1
                        else [jax.devices()[0]])
        fn = sim.make_experiment_fn(loss, cfg, self.seg, eval_fn=eval_fn,
                                    eval_every=fz["eval_every"],
                                    ring_size=self.seg, round_fn=round_fn)
        self.cfg = cfg
        self.load(inputs)
        t = time.perf_counter()
        self.compiled = fn.lower(*self.carry, self.store).compile()
        self.compile_s = time.perf_counter() - t
        if require_chip and fz["flat_params"] and \
                "tpu_custom_call" not in self.compiled.as_text():
            raise RuntimeError("the flat-kernel plan compiled without its "
                               "Pallas kernels (no tpu_custom_call)")

    def load(self, inputs):
        """Point the program at a seed's inputs: the store and a fresh
        carry (the compiled program is kept)."""
        import jax

        from repro import sim

        clients, sizes, _, params0, run_key = inputs
        if self.replicated is not None:
            clients, sizes, params0, run_key = jax.device_put(
                (clients, sizes, params0, run_key), self.replicated)
        model = self.cfg.channel_model
        cstate = (model.init_state(self.fz["n_clients"],
                                   sim.channel.init_key(run_key))
                  if model is not None else None)
        self.store = sim.ClientStore(data=clients, sizes=sizes)
        self.carry = (params0, None, run_key, None, cstate, None)

    def segment(self):
        """Run one segment and keep its carry; returns the program's whole
        output (the carry, then the metrics ring and the evals)."""
        out = self.compiled(*self.carry, self.store)
        self.carry = out[:6]
        return out

    def free(self):
        self.carry = self.compiled = self.store = None


def host(tree):
    import jax
    import numpy as np
    return jax.tree.map(np.asarray, tree)


def setup_steps(prog: Program) -> dict:
    """The first STEPS segments through the window's own call: per-round
    metrics, the in-scan test losses laid out by round, and the parameters
    after each segment (copied to the host before the next call donates
    them)."""
    import jax
    import numpy as np

    seg, every = prog.seg, prog.fz["eval_every"]
    mets, params = [], []
    for _ in range(STEPS):
        out = jax.block_until_ready(prog.segment())
        ring, evals = host(out[6]), host(out[7])
        ev = np.full(seg, np.nan, np.float32)
        ev[::every] = evals["test_loss"]
        ring["eval_loss"] = ev
        mets.append(ring)
        params.append(host(prog.carry[0]))
    keys = [k for k in mets[0] if all(k in m for m in mets)]
    return {"metrics": {k: np.concatenate([m[k] for m in mets])
                        for k in keys}, "params": params}


def reference(spec: dict, seed: int, *, precision="highest", fault=None,
              inputs=None):
    """The plain reference over the same STEPS segments, from the same
    seed-made inputs."""
    import fedref

    clients, sizes, test_eval, params0, run_key = (
        inputs if inputs is not None else make_inputs(spec, seed))
    metrics, per_step = fedref.run(
        spec["model"].ref_logits, spec["fz"], params0, run_key, clients,
        sizes, test_eval, STEPS, precision=precision, fault=fault)
    return {"metrics": metrics, "params": per_step}


class CompileCounter:
    """Counts traces and backend compiles while armed."""

    def __init__(self):
        import jax
        self.armed, self.count = False, 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/core/compile/jaxpr_trace_duration")

    def _on(self, name, _dur, **_kw):
        if self.armed and name in self.EVENTS:
            self.count += 1


def window(prog: Program, seconds: float):
    """Segments back to back until ``seconds`` have passed. Returns
    (segment seconds list, window seconds, rings of the segments)."""
    import jax

    times, rings = [], []
    start = time.perf_counter()
    while True:
        t = time.perf_counter()
        out = jax.block_until_ready(prog.segment())
        now = time.perf_counter()
        times.append(now - t)
        rings.append(out[6]["mean_local_loss"])
        if now - start >= seconds:
            return times, now - start, rings


def traced_window(prog: Program, n_segments: int, out_dir: Path):
    """``n_segments`` segments under the profiler, with host spans around
    the dispatch and the wait of each. Returns (xplane path, seconds)."""
    import glob

    import jax

    shutil.rmtree(out_dir, ignore_errors=True)
    jax.profiler.start_trace(str(out_dir))
    try:
        start = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.window"):
            for _ in range(n_segments):
                with jax.profiler.TraceAnnotation("bench.dispatch"):
                    out = prog.segment()
                with jax.profiler.TraceAnnotation("bench.wait"):
                    jax.block_until_ready(out)
        seconds = time.perf_counter() - start
    finally:
        jax.profiler.stop_trace()
    return glob.glob(f"{out_dir}/**/*.xplane.pb", recursive=True)[0], seconds


def per_layer(spec, trace, rounds, peaks, kind):
    """Each per-layer metric's reader over the reduced trace."""
    import costs

    fz = spec["fz"]
    ctx = {"trace": trace, "rounds": rounds, "window_s": trace["window_s"],
           "chips": fz.get("mesh_clients", 1), "peak": peaks["kinds"][kind],
           "fz": fz,
           "flops_per_round": costs.forward_flops_per_round(
               fz, spec["model"].flops_per_sample(fz)),
           "kernel_bytes_per_round": costs.kernel_bytes_per_round(fz)}
    out = {}
    for m in spec["per_layer"]:
        reader = load_module(spec["metric_readers"][m["name"]],
                             "bench_metric_" + m["name"].replace(".", "_"))
        v = reader.read(ctx)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def _finite(v):
    return v if math.isfinite(v) else 1e30


def run_cell(workload, seed, seconds, trace=0, *, require_chip=True,
             overrides=None, root=ROOT):
    """One run of a cell. Returns the result dict (the last stdout line) or
    raises NoChip. ``require_chip=False`` and ``overrides`` (keys of the
    configuration replaced, to shrink it) are for the CPU tests only."""
    spec = resolve(workload, root)
    spec["fz"].update(overrides or {})
    peaks = _read(BENCH / "peaks.json")
    from repro.utils import compile_cache
    cache_dir = compile_cache.enable()
    import jax
    import numpy as np

    import check
    check_precision(spec["fz"])
    if require_chip:
        devs = check_chip(spec["cell"]["chips"], peaks)
    else:
        devs = jax.devices()
    kind = devs[0].device_kind
    log(f"bench: {workload} seed={seed} device={kind} count={len(devs)} "
        f"jax={jax.__version__} cache={cache_dir}")

    counter = CompileCounter()
    parts = {"start": time.perf_counter() - _T0}
    inputs = jax.block_until_ready(make_inputs(spec, seed))
    params0 = host(inputs[3])
    parts["inputs"] = time.perf_counter() - _T0
    prog = Program(spec, seed, inputs, require_chip=require_chip)
    parts["compiled"] = time.perf_counter() - _T0
    first = setup_steps(prog)
    setup_s = time.perf_counter() - _T0
    log(f"bench: setup_s={setup_s:.3f} compile_s={prog.compile_s:.3f} "
        f"segment_rounds={prog.seg} " + " ".join(
            f"{k}_at={v:.3f}" for k, v in parts.items()))

    # a collection of the set-up's garbage inside the window would be the
    # harness's pause, not the program's
    gc.collect()
    gc.freeze()
    counter.armed = True
    extra = {}
    if trace:
        import trace_reduce

        t = time.perf_counter()
        jax.block_until_ready(prog.segment())
        est = time.perf_counter() - t
        n_seg = max(2, math.ceil(TRACE_SECONDS / max(est, 1e-3)))
        path, win = traced_window(prog, n_seg, root / ".bench_trace")
        red = trace_reduce.reduce(path)
        metrics = per_layer(spec, red, n_seg * prog.seg, peaks, kind)
        attempted, failed = n_seg, 0
        extra["breakdown"] = red["breakdown"]
        log(f"bench: traced {n_seg} segments host_s={win:.4f} "
            f"window_s={red['window_s']:.6f} busy_s={red['busy_s']:.6f}")
    else:
        times, win, rings = window(prog, seconds)
        losses = np.stack([np.asarray(r) for r in rings])
        failed = int(np.sum(~np.all(np.isfinite(losses), axis=1)))
        attempted = len(times)
        rounds = attempted * prog.seg
        p90 = (statistics.quantiles(times, n=10)[-1] if len(times) > 1
               else times[0])
        med = statistics.median(times)
        worst = max(range(attempted), key=times.__getitem__)
        print(f"segments={attempted} rounds={rounds} window_s={win:.6f} "
              f"segment_ms_median={1e3 * med:.4f} "
              f"segment_ms_p90={1e3 * p90:.4f} "
              f"segment_ms_max={1e3 * times[worst]:.4f} "
              f"max_at_segment={worst} "
              f"max_at_s={sum(times[:worst]):.3f} "
              f"segments_over_2x_median="
              f"{sum(t > 2 * med for t in times)}", flush=True)
        vals = {"rounds_per_s": rounds / win, "segment_ms_p90": 1e3 * p90,
                "setup_s": setup_s}
        metrics = {m["name"]: {"value": vals[m["name"]], "unit": m["unit"]}
                   for m in spec["end_to_end"]}
    counter.armed = False
    print(f"compiles_in_window={counter.count}", flush=True)
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in prog.devices)
    prog.free()
    del prog

    # the carry donated the program's copies of the weights and the key
    t = time.perf_counter()
    ref = reference(spec, seed, inputs=inputs[:3] + (params0, run_key(seed)))
    log(f"bench: reference_s={time.perf_counter() - t:.3f}")
    values = check.compare(first, ref, params0,
                           channel=spec["fz"].get("channel") is not None)
    ok, checks = check.judge(values, spec["limits"])
    ok = ok and failed == 0 and counter.count == 0
    device = {"platform": devs[0].platform, "kind": kind, "count": len(devs),
              "memory_peak_bytes": int(peak)}
    if trace:
        device.update(busy_s=red["busy_s"], window_s=red["window_s"])
    for k, c in checks.items():
        log(f"check {k}={c['value']:.6e} limit={c['limit']}")
    return {"correct": bool(ok), "attempted": attempted, "failed": failed,
            "metrics": metrics, "device": device, **extra,
            "checks": {k: {"value": _finite(c["value"]), "limit": c["limit"]}
                       for k, c in checks.items()}}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    # the compile cache the program is given: a fixed directory inside the
    # checkout, whatever the environment says. JAX does not create it, and
    # without it every run compiles again.
    cache = ROOT / ".jax_cache"
    cache.mkdir(exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(cache)
    try:
        result = run_cell(args.workload, args.seed, args.seconds, args.trace)
    except NoChip as e:
        log(f"bench: {e}")
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
