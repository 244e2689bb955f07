"""Host-loop vs in-jit federation engine (DESIGN.md §9) on the quickstart
softmax-regression experiment (N=50, M=10, H=5, b1=25, b2=20, d=7850).

Rows:

- ``sim/host_loop_us_per_round``   — the per-round Python ``FedServer.run``
  loop as it ships (numpy sampling, host batch stacking, one jit entry per
  round, per-round metric sync), measured over SIM_BENCH_ROUNDS rounds.
- ``sim/engine_us_per_round``      — the same experiment as ONE compiled
  scan (``sim.run_experiment`` under ``sim.fast_sim_config``: in-jit
  store sampling, batched-direction local phases, donated carry), steady
  state (compile excluded).
- ``sim/engine_loop_est_us_per_round`` — the engine scanning the UNCHANGED
  loop-estimator round: isolates the structural scan/store gain from the
  batched-direction gain (measured over fewer rounds; per-round metric).
- ``sim/engine_speedup_x``         — host loop / fast engine (the ≥5×
  acceptance row).
- ``sim/engine_tap_us_per_round`` / ``sim/tap_overhead_pct`` — the engine
  with a worst-case in-scan telemetry tap (``tap_every=1`` into a
  NullSink, one io_callback per round) vs taps-off (<10% acceptance).
- ``sim/sharded_dev{n}_us_per_round`` — the clients-axis shard_map round
  inside the engine over the process's own n devices (the most that divide
  M). The CPU sharding check on forced host devices lives in
  ``tests/test_sim.py``.
- ``tiered/*`` (``run_tiered``, snapshot ``BENCH_tiered.json``) — the
  host-resident HostStore streaming engine vs the resident scan on the
  same experiment, plus an N=100k-client CPU run with prefetch-stall and
  host/device residency accounting (DESIGN.md §15).

CPU numbers are regression trackers, not TPU projections (§6).
"""
from __future__ import annotations

import os
import time

import jax
import numpy as np

ROUNDS = int(os.environ.get("SIM_BENCH_ROUNDS", "50"))


def _quickstart_setup():
    import jax.numpy as jnp
    from repro.configs.base import FedZOConfig
    from repro.data.synthetic import make_classification, noniid_shards

    x, y = make_classification(7000, 784, 10, seed=0)
    clients = noniid_shards(x[:6000], y[:6000], 50)
    cfg = FedZOConfig(n_devices=50, n_participating=10, local_iters=5,
                      lr=1e-3, mu=1e-3, b1=25, b2=20)
    del jnp
    return clients, cfg


def _sharded_row(store, cfg, p0, rounds=10):
    """Time the sharded engine round over this process's own devices (as
    many as divide M). It runs in-process: on a TPU the process that has
    run JAX holds the chips, so a child would find none."""
    from repro import sim
    from repro.models.simple import softmax_loss

    n_dev = len(jax.devices())
    while cfg.n_participating % n_dev:
        n_dev -= 1
    rf = sim.make_sharded_round(softmax_loss, cfg,
                                sim.make_clients_mesh(n_dev))
    fn = sim.make_experiment_fn(softmax_loss, cfg, rounds, round_fn=rf,
                                donate=False)
    key = sim.experiment_key(cfg)
    out = fn(p0, None, key, None, None, None, store)  # compile
    jax.block_until_ready(out[0])
    t0 = time.perf_counter()
    out = fn(p0, None, key, None, None, None, store)
    jax.block_until_ready(out[0])
    return n_dev, (time.perf_counter() - t0) / rounds * 1e6


def run():
    from repro import sim
    from repro.fed.server import FedServer
    from repro.models.simple import softmax_init, softmax_loss

    rows = []
    clients, cfg = _quickstart_setup()

    # -- host loop (the reference FedServer.run python path) ------------------
    srv = FedServer(softmax_loss, softmax_init(None), clients, cfg)
    srv.run_round(0)                                  # compile
    t0 = time.perf_counter()
    srv.run(ROUNDS, driver="host")
    host_us = (time.perf_counter() - t0) / ROUNDS * 1e6
    rows.append(("sim/host_loop_us_per_round", host_us, ROUNDS))

    # -- in-jit engine, fast execution plan -----------------------------------
    store = sim.build_store(clients)
    fcfg = sim.fast_sim_config(cfg)
    fn = sim.make_experiment_fn(softmax_loss, fcfg, ROUNDS, donate=False)
    key = sim.experiment_key(fcfg)
    p0 = softmax_init(None)
    out = fn(p0, None, key, None, None, None, store)  # compile
    jax.block_until_ready(out[0])
    t0 = time.perf_counter()
    out = fn(p0, None, key, None, None, None, store)
    jax.block_until_ready(out[0])
    eng_us = (time.perf_counter() - t0) / ROUNDS * 1e6
    rows.append(("sim/engine_us_per_round", eng_us, ROUNDS))
    rows.append(("sim/engine_speedup_x", 0.0, host_us / eng_us))

    # -- engine scanning the UNCHANGED loop-estimator round -------------------
    r_loop = max(2, ROUNDS // 10)
    fn2 = sim.make_experiment_fn(softmax_loss, cfg, r_loop, donate=False)
    out = fn2(p0, None, sim.experiment_key(cfg), None, None, None, store)
    jax.block_until_ready(out[0])
    t0 = time.perf_counter()
    out = fn2(p0, None, sim.experiment_key(cfg), None, None, None, store)
    jax.block_until_ready(out[0])
    rows.append(("sim/engine_loop_est_us_per_round",
                 (time.perf_counter() - t0) / r_loop * 1e6, r_loop))

    # -- in-scan tap overhead (acceptance: <10% on µs/round) ------------------
    # tap_every=1 (every round fires the io_callback) into a NullSink is
    # the worst case; real cadences (tap_every=10+) amortize further
    from repro import obs
    tap = obs.RoundTap(obs.NullSink(), 1)
    fnt = sim.make_experiment_fn(softmax_loss, fcfg, ROUNDS, donate=False,
                                 tap=tap)
    out = fnt(p0, None, key, None, None, None, store)  # compile
    jax.block_until_ready(out[0])
    t0 = time.perf_counter()
    out = fnt(p0, None, key, None, None, None, store)
    jax.block_until_ready(out[0])
    tap_us = (time.perf_counter() - t0) / ROUNDS * 1e6
    rows.append(("sim/engine_tap_us_per_round", tap_us, ROUNDS))
    rows.append(("sim/tap_overhead_pct", 0.0,
                 (tap_us / eng_us - 1.0) * 100.0))

    # -- fault-injection layer overhead (acceptance: <5% on rounds/s) ---------
    faults = sim.FaultModel(p_fail=0.05, p_recover=0.4, deadline=2.0,
                            p_corrupt=0.02)
    fstate = faults.init_state(store.n_clients)
    fnf = sim.make_experiment_fn(softmax_loss, fcfg, ROUNDS, faults=faults,
                                 donate=False)
    out = fnf(p0, None, key, fstate, None, None, store)  # compile
    jax.block_until_ready(out[0])
    t0 = time.perf_counter()
    out = fnf(p0, None, key, fstate, None, None, store)
    jax.block_until_ready(out[0])
    faults_us = (time.perf_counter() - t0) / ROUNDS * 1e6
    rows.append(("sim/engine_faults_us_per_round", faults_us, ROUNDS))
    rows.append(("sim/faults_overhead_pct", 0.0,
                 (faults_us / eng_us - 1.0) * 100.0))

    # -- the sharded round over this process's devices -------------------------
    n_dev, us = _sharded_row(store, fcfg, p0)
    rows.append((f"sim/sharded_dev{n_dev}_us_per_round", us, n_dev))
    return rows


# strategy-name -> config overrides on top of the fast engine plan; every
# variant runs the SAME experiment shape so overhead-vs-fedzo is pure
# algorithm cost (loss wrap, state gather/scatter, server correction)
ALGO_VARIANTS = (
    ("fedzo", {}),
    ("fedprox", {"strategy": "fedprox", "prox_mu": 0.01}),
    ("feddyn", {"strategy": "feddyn", "dyn_alpha": 0.01}),
    ("scaffold", {"strategy": "scaffold"}),
    ("fedzo_surrogate", {"direction_conv": "surrogate"}),
)


def run_algos():
    """Per-strategy engine cost: µs/round for each registered ZO strategy
    (+ the surrogate estimator) on the quickstart experiment under the fast
    engine plan, plus its overhead vs plain FedZO in %. (The harness —
    benchmarks/run.py — snapshots these rows to ``results/BENCH_algos.json``
    via ``obs.save_bench``, same as every other suite.)"""
    import dataclasses

    from repro import sim
    from repro.models.simple import softmax_init, softmax_loss

    rows = []
    clients, cfg = _quickstart_setup()
    store = sim.build_store(clients)
    rounds = max(2, ROUNDS // 2)
    base_us = None
    for name, overrides in ALGO_VARIANTS:
        acfg = dataclasses.replace(sim.fast_sim_config(cfg), **overrides)
        fn = sim.make_experiment_fn(softmax_loss, acfg, rounds, donate=False)
        key = sim.experiment_key(acfg)
        p0 = softmax_init(None)
        from repro.core import strategy as strategy_mod
        zstate = strategy_mod.get(acfg.strategy).init_state(p0, acfg,
                                                            store.n_clients)
        out = fn(p0, None, key, None, None, zstate, store)  # compile
        jax.block_until_ready(out[0])
        t0 = time.perf_counter()
        out = fn(p0, None, key, None, None, zstate, store)
        jax.block_until_ready(out[0])
        us = (time.perf_counter() - t0) / rounds * 1e6
        rows.append((f"algos/{name}_us_per_round", us, rounds))
        if name == "fedzo":
            base_us = us
        else:
            rows.append((f"algos/{name}_overhead_vs_fedzo_pct", 0.0,
                         (us / base_us - 1.0) * 100.0))
    return rows


def run_scenario():
    """Wireless-scenario engine cost (DESIGN.md §16): the correlated-fading
    chain and energy-gated participation vs the channel-off i.i.d. draw on
    the quickstart experiment under channel scheduling. Rows (snapshot
    ``results/BENCH_scenario.json`` via the harness):

    - ``scenario/channel_off_us_per_round`` — i.i.d. per-round draw (the
      paper's Sec. IV-A baseline) under the fast engine plan.
    - ``scenario/fading_us_per_round`` / ``_overhead_pct`` — the AR(1)
      chain (ρ=0.9) carried through the scan.
    - ``scenario/gated_us_per_round`` / ``_overhead_pct`` — fading plus
      battery gating with a budget that drains mid-run, and
      ``scenario/gated_m_effective_mean`` — the mean surviving cohort the
      drain produces (the row that shows the gate actually bites)."""
    import dataclasses

    from repro import sim
    from repro.models.simple import softmax_init, softmax_loss

    rows = []
    clients, cfg = _quickstart_setup()
    store = sim.build_store(clients)
    p0 = softmax_init(None)
    rounds = max(4, ROUNDS // 2)
    base = dataclasses.replace(sim.fast_sim_config(cfg),
                               channel_schedule=True, h_min=0.3)

    def timed(c):
        from repro.sim import channel as channel_lib
        fn = sim.make_experiment_fn(softmax_loss, c, rounds, donate=False)
        key = sim.experiment_key(c)
        cm = c.channel_model
        cstate = (cm.init_state(store.n_clients, channel_lib.init_key(key))
                  if cm is not None else None)
        out = fn(p0, None, key, None, cstate, None, store)  # compile
        jax.block_until_ready(out[0])
        t0 = time.perf_counter()
        out = fn(p0, None, key, None, cstate, None, store)
        jax.block_until_ready(out[0])
        return (time.perf_counter() - t0) / rounds * 1e6, out

    off_us, _ = timed(base)
    rows.append(("scenario/channel_off_us_per_round", off_us, rounds))

    fad_us, _ = timed(dataclasses.replace(
        base, channel_model=sim.ChannelModel(rho=0.9)))
    rows.append(("scenario/fading_us_per_round", fad_us, rounds))
    rows.append(("scenario/fading_overhead_pct", 0.0,
                 (fad_us / off_us - 1.0) * 100.0))

    gm = sim.ChannelModel(rho=0.9, battery=float(max(2, rounds // 2)),
                          tx_cost=1.0)
    gat_us, out = timed(dataclasses.replace(base, channel_model=gm))
    ring = out[6]
    rows.append(("scenario/gated_us_per_round", gat_us, rounds))
    rows.append(("scenario/gated_overhead_pct", 0.0,
                 (gat_us / off_us - 1.0) * 100.0))
    rows.append(("scenario/gated_m_effective_mean", 0.0,
                 round(float(np.mean(np.asarray(ring["m_effective"]))), 2)))
    return rows


def _ragged_population(n_clients, lo, hi, n_features=24, n_classes=4,
                       seed=0):
    """A size-skewed synthetic federation at arbitrary N — the tiered
    store's regime. Row counts are drawn uniform [lo, hi); features come
    from one make_classification pool sliced per client."""
    from repro.data.synthetic import make_classification

    rng = np.random.default_rng(seed)
    sizes = rng.integers(lo, hi, size=n_clients)
    x, y = make_classification(int(sizes.sum()), n_features, n_classes,
                               seed=seed)
    clients, off = [], 0
    for s in sizes:
        clients.append({"x": x[off:off + s], "y": y[off:off + s]})
        off += s
    return clients


def run_tiered():
    """Tiered HostStore vs resident engine (DESIGN.md §15).

    Quickstart-scale rows measure the streaming overhead against the
    device-resident scan on the SAME (bitwise-identical) experiment:
    ``tiered/engine_us_per_round`` + ``tiered/overhead_vs_resident_pct``,
    plus the prefetch-stall and memory-residency accounting
    (``prefetch_stall_pct``, ``host_bytes``, ``device_bytes`` — the staged
    segment + one prefetch buffer is ALL the population data on device).

    The ``tiered/scale100k_*`` rows run N=100k clients (ragged, bucketed)
    on CPU — far past what the resident store's [N, cap] layout would
    admit alongside itself — and report the same stall/residency numbers
    (``TIERED_BENCH_CLIENTS`` scales N)."""
    from repro import sim
    from repro.models.simple import softmax_init, softmax_loss

    rows = []
    clients, cfg = _quickstart_setup()
    fcfg = sim.fast_sim_config(cfg)
    p0 = softmax_init(None)
    rounds = max(4, ROUNDS // 2)

    store = sim.build_store(clients)
    res = sim.run_experiment(softmax_loss, p0, store, fcfg, rounds,
                             donate=False)            # compile
    jax.block_until_ready(res.params["w"])
    t0 = time.perf_counter()
    res = sim.run_experiment(softmax_loss, p0, store, fcfg, rounds,
                             donate=False)
    jax.block_until_ready(res.params["w"])
    res_us = (time.perf_counter() - t0) / rounds * 1e6
    rows.append(("tiered/resident_us_per_round", res_us, rounds))

    host = sim.build_host_store(clients, n_buckets=4)
    tier = sim.run_experiment(softmax_loss, p0, host, fcfg, rounds,
                              donate=False)           # compile
    jax.block_until_ready(tier.params["w"])
    t0 = time.perf_counter()
    tier = sim.run_experiment(softmax_loss, p0, host, fcfg, rounds,
                              donate=False)
    jax.block_until_ready(tier.params["w"])
    tier_us = (time.perf_counter() - t0) / rounds * 1e6
    pf = tier.prefetch
    rows.append(("tiered/engine_us_per_round", tier_us, rounds))
    rows.append(("tiered/overhead_vs_resident_pct", 0.0,
                 (tier_us / res_us - 1.0) * 100.0))
    rows.append(("tiered/prefetch_stall_pct", 0.0,
                 round(pf["stall_pct"], 2)))
    rows.append(("tiered/host_bytes", 0.0, pf["host_bytes"]))
    rows.append(("tiered/device_bytes", 0.0,
                 pf["device_segment_bytes_max"]))

    # -- N=100k: the regime the resident tier cannot reach ---------------
    n_big = int(os.environ.get("TIERED_BENCH_CLIENTS", "100000"))
    big = _ragged_population(n_big, 6, 13, seed=1)
    import dataclasses
    bcfg = dataclasses.replace(fcfg, n_devices=n_big, n_participating=32,
                               b1=4, local_iters=2)
    bstore = sim.build_host_store(big, n_buckets=4)
    del big
    b_rounds = 6
    bp0 = softmax_init(None, 24, 4)
    bres = sim.run_experiment(softmax_loss, bp0, bstore, bcfg, b_rounds,
                              donate=False)
    jax.block_until_ready(bres.params["w"])
    bpf = bres.prefetch
    rows.append(("tiered/scale100k_us_per_round",
                 bpf["wall_s"] / b_rounds * 1e6, n_big))
    rows.append(("tiered/scale100k_prefetch_stall_pct", 0.0,
                 round(bpf["stall_pct"], 2)))
    rows.append(("tiered/scale100k_host_bytes", 0.0, bpf["host_bytes"]))
    rows.append(("tiered/scale100k_device_bytes", 0.0,
                 bpf["device_segment_bytes_max"]))
    return rows
