"""repro.obs: in-scan taps, trace spans, comms ledger, manifests
(DESIGN.md §14).

Pins the subsystem's contracts:

- **Taps don't perturb the run**: an engine run with ``tap_every=k``
  produces bit-identical final params/metrics to the taps-off run (the
  io_callback only OBSERVES the round's metrics), and the streamed JSONL
  rows bitwise-match the final ring via ``history()``.
- **Spans separate compile from execute**: one ``compile`` span per static
  shape (the checkpointed runner reuses its executable across same-size
  segments), spans nest with correct depth/parent, and every span is also
  a profiler annotation on the trace's host plane.
- **Named scopes reach the compiled program**: every registered scope is on
  the ``op_name`` paths of a round's compiled HLO, the loss queries sit
  inside the local phase, and an unregistered name is refused.
- **Ledger columns are deterministic in t**: ring-limited and full runs
  annotate identically; the seed-path byte model equals the measured
  ``seedcomm.wire_bytes`` of an actual compressed message.
- **Manifests cross-check with checkpoints**: the run manifest's
  ``config_hash`` equals the snapshot sidecar's.
"""
from __future__ import annotations

import glob
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs, sim
from repro.configs.base import FedZOConfig
from repro.data.synthetic import make_classification, noniid_shards
from repro.models.simple import softmax_init, softmax_loss
from repro.sim import engine


def _setup(n=320, n_clients=4, n_features=12, n_classes=3, seed=0):
    x, y = make_classification(n, n_features, n_classes, seed=seed)
    clients = noniid_shards(x, y, n_clients)
    return sim.build_store(clients)


def _cfg(**kw):
    base = dict(n_devices=4, n_participating=2, local_iters=2, lr=1e-2,
                mu=1e-3, b1=4, b2=2, seed=3)
    base.update(kw)
    return FedZOConfig(**base)


def _params():
    return softmax_init(None, 12, 3)


def _assert_trees_bitequal(a, b):
    for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))


# ---------------------------------------------------------------------------
# sinks


def test_jsonl_sink_roundtrip(tmp_path):
    path = os.path.join(tmp_path, "rows.jsonl")
    rows = [{"round": 0, "loss": 1.5, "ok": True},
            {"round": 1, "loss": 0.75, "ok": False}]
    with obs.JsonlSink(path) as sink:
        for r in rows:
            sink.write(r)
    assert obs.read_jsonl(path) == rows
    # every line is standalone JSON (tail -f consumable)
    with open(path) as f:
        for line in f:
            json.loads(line)


def test_memory_null_multi_csv_sinks(tmp_path):
    mem, null = obs.MemorySink(), obs.NullSink()
    csv_path = os.path.join(tmp_path, "rows.csv")
    csv = obs.CsvSink(csv_path)
    multi = obs.MultiSink(mem, null, csv)
    multi.write({"round": 0, "loss": 2.0})
    multi.write({"round": 1, "loss": 1.0})
    multi.close()
    assert [r["round"] for r in mem.rows] == [0, 1]
    assert null.count == 2
    lines = open(csv_path).read().splitlines()
    assert lines[0] == "round,loss" and len(lines) == 3


# ---------------------------------------------------------------------------
# in-scan taps


def test_taps_do_not_perturb_and_rows_match_history(tmp_path):
    store, cfg, p0 = _setup(), _cfg(), _params()
    rounds, every = 8, 2
    base = engine.run_experiment(softmax_loss, p0, store, cfg, rounds,
                                 donate=False)
    path = os.path.join(tmp_path, "live.jsonl")
    sink = obs.JsonlSink(path)
    tapped = engine.run_experiment(softmax_loss, p0, store, cfg, rounds,
                                   donate=False, sink=sink,
                                   tap_every=every)
    sink.close()
    # the tap only observes: bit-identical params, key, and metrics ring
    _assert_trees_bitequal(base.params, tapped.params)
    _assert_trees_bitequal(jax.random.key_data(base.key),
                           jax.random.key_data(tapped.key))
    _assert_trees_bitequal(base.metrics, tapped.metrics)

    rows = obs.read_jsonl(path)
    assert len(rows) >= rounds // every                # ≥ R/k acceptance
    assert [r["round"] for r in rows] == list(range(0, rounds, every))
    # streamed rows bitwise-match the final ring (via history)
    hist = {r["round"]: r for r in engine.history(tapped)}
    for row in rows:
        want = hist[row["round"]]
        for k, v in row.items():
            if k == "round":
                continue
            assert v == want[k], (k, v, want[k])
    # manifest landed beside the file sink, hash matches the run config
    man = obs.read_manifest(f"{path}.manifest.json")
    from repro.checkpoint.checkpoint import config_hash
    assert man["config_hash"] == config_hash(cfg)
    assert man["tap_every"] == every
    assert man["comms"]["mode"] == "dense"


def test_tap_requires_sink():
    store, cfg, p0 = _setup(), _cfg(), _params()
    with pytest.raises(ValueError, match="sink"):
        engine.run_experiment(softmax_loss, p0, store, cfg, 2,
                              donate=False, tap_every=1)
    with pytest.raises(ValueError, match="tap_every"):
        obs.RoundTap(obs.NullSink(), 0)


# ---------------------------------------------------------------------------
# tracer spans


def test_spans_nest():
    tr = obs.Tracer()
    with tr.span("outer"):
        with tr.span("inner", tag=1):
            pass
        with tr.span("inner2"):
            pass
    outer, inner, inner2 = tr.spans
    assert (outer.depth, inner.depth, inner2.depth) == (0, 1, 1)
    assert inner.parent == 0 and inner2.parent == 0
    assert outer.duration >= inner.duration + 0.0
    assert tr.totals()["inner"]["count"] == 1
    assert "inner tag=1" not in tr.report()  # meta rendered k=v
    assert "tag=1" in tr.report()


def test_tracer_compile_once_and_execute_span():
    store, cfg, p0 = _setup(), _cfg(), _params()
    tr = obs.Tracer()
    r1 = engine.run_experiment(softmax_loss, p0, store, cfg, 4,
                               donate=False, tracer=tr)
    r2 = engine.run_experiment(softmax_loss, p0, store, cfg, 4,
                               donate=False, tracer=tr)
    # same static shape twice -> exactly ONE compile span, two executes
    assert len(tr.named("compile")) == 1
    assert tr.named("compile")[0].duration > 0
    assert len(tr.named("execute")) == 2
    _assert_trees_bitequal(r1.params, r2.params)
    # the AOT-compiled run equals the plain jit run bit for bit
    plain = engine.run_experiment(softmax_loss, p0, store, cfg, 4,
                                  donate=False)
    _assert_trees_bitequal(plain.params, r1.params)


# ---------------------------------------------------------------------------
# checkpointed runner: segments, manifest/sidecar cross-check


def test_checkpointed_spans_manifest_and_taps(tmp_path):
    store, cfg, p0 = _setup(), _cfg(), _params()
    rounds, every = 8, 2
    base = engine.run_experiment(softmax_loss, p0, store, cfg, rounds,
                                 donate=False)
    tr, ms = obs.Tracer(), obs.MemorySink()
    ckdir = os.path.join(tmp_path, "ck")
    res = engine.run_experiment(softmax_loss, p0, store, cfg, rounds,
                                donate=False, checkpoint_every=4,
                                checkpoint_dir=ckdir, sink=ms,
                                tap_every=every, tracer=tr)
    _assert_trees_bitequal(base.params, res.params)
    # two same-size segments share ONE compiled program -> 1 compile span,
    # 2 segment spans, compile strictly positive
    assert len(tr.named("compile")) == 1
    assert tr.named("compile")[0].duration > 0
    assert len(tr.named("segment")) == 2
    assert [s.meta["t0"] for s in tr.named("segment")] == [0, 4]
    # taps fired across segment boundaries on the global round index
    assert [r["round"] for r in ms.rows] == list(range(0, rounds, every))
    # manifest beside the checkpoints; hash cross-checks with the sidecar
    from repro.checkpoint import checkpoint as ckpt
    man = obs.read_manifest(ckdir)
    side = ckpt.read_sidecar(ckpt.latest_run_state(ckdir))
    assert man["config_hash"] == side["config_hash"]
    assert man["rounds_done"] == rounds
    assert man["strategy"] == "fedzo"
    assert res.manifest["rounds_done"] == rounds


def _host_events(profile_dir, names):
    """(name, start_ns, end_ns, stats) of the host-plane events with the
    given names in a profiler trace directory."""
    from jax.profiler import ProfileData
    path, = glob.glob(f"{profile_dir}/**/*.xplane.pb", recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out.extend((e.name, e.start_ns, e.start_ns + e.duration_ns,
                            dict(e.stats))
                           for e in line.events if e.name in names)
    return out


def test_checkpointed_spans_on_profiler_clock(tmp_path):
    """Each segment's host phases are profiler annotations: segment holds
    segment.dispatch, segment.fetch and checkpoint.save, all carrying the
    segment's first round as t0."""
    store, cfg, p0 = _setup(), _cfg(), _params()
    prof = str(tmp_path / "prof")
    tr = obs.Tracer(profile_dir=prof)
    engine.run_experiment(softmax_loss, p0, store, cfg, 8, donate=False,
                          checkpoint_every=4,
                          checkpoint_dir=str(tmp_path / "ck"), tracer=tr)
    kids = ("segment.dispatch", "segment.fetch", "checkpoint.save")
    evs = _host_events(prof, ("segment",) + kids)
    segs = sorted(e for e in evs if e[0] == "segment")
    assert [e[3]["t0"] for e in segs] == [0, 4]
    for name, s, e, stats in segs:
        inside = [k for k in evs
                  if k[0] in kids and k[3]["t0"] == stats["t0"]]
        assert sorted(k[0] for k in inside) == sorted(kids)
        assert all(s <= ks and ke <= e for _, ks, ke, _ in inside)
        order = [k[0] for k in sorted(inside, key=lambda k: k[1])]
        assert order == list(kids)
    # the host-clock record holds the same tree
    for name in kids:
        assert [tr.spans[s.parent].name for s in tr.named(name)] == \
            ["segment", "segment"], name


def test_tiered_stage_wait_span():
    """The tiered runner names its wait for a staged cohort."""
    x, y = make_classification(320, 12, 3, seed=0)
    host = sim.build_host_store(noniid_shards(x, y, 4), n_buckets=1)
    tr = obs.Tracer()
    engine.run_experiment(softmax_loss, _params(), host, _cfg(), 4,
                          donate=False, stream_segment=2, tracer=tr)
    waits = tr.named("tiered.stage_wait")
    assert [s.meta["t0"] for s in waits] == [0, 2]
    assert [s.meta["t0"] for s in tr.named("tiered_segment")] == [0, 2]


# ---------------------------------------------------------------------------
# named scopes in the compiled program


def _scope_paths(text):
    """The registered scopes on each instruction's op_name path of a
    compiled program, for the paths that start at the program's root (a
    reduction's body computation repeats its caller's relative path)."""
    out = []
    for path in re.findall(r'op_name="(jit\([^"]*)"', text):
        parts = set(re.split(r"[/()]", path))
        out.append({s for s in obs.SCOPES if s in parts})
    return out


def _experiment_text(**kw):
    from repro.workloads import neural
    task = neural.make_task("softmax", n_train=240, n_test=64, n_clients=6,
                            n_features=24, n_classes=4)
    cfg = neural.default_config(
        task, n_participating=3, local_iters=2, b1=4, b2=3, lr=1e-2,
        aircomp=True, channel_model=sim.ChannelModel.from_doppler(0.02),
        **kw)

    def eval_fn(p):
        return {"test_loss": task.loss(p, task.test)}

    fn = sim.make_experiment_fn(task.loss, cfg, 2, eval_fn=eval_fn,
                                eval_every=1)
    key = jax.random.key(0)
    cstate = cfg.channel_model.init_state(task.store.n_clients,
                                          sim.channel.init_key(key))
    return fn.lower(task.init(0), None, key, None, cstate, None,
                    task.store).compile().as_text()


@pytest.mark.parametrize("plan", [
    {"flat_params": True, "flat_block_rows": 8},
    {},
    {"batch_directions": True, "direction_conv": "block"},
], ids=["flat", "pytree", "wide"])
def test_every_scope_in_compiled_experiment(plan):
    """AirComp over a fading channel with an in-scan eval runs every layer
    of a round, on each local-phase plan."""
    paths = _scope_paths(_experiment_text(**plan))
    seen = set().union(*paths)
    assert seen == set(obs.SCOPES)


def test_query_scope_nested_in_local_phase():
    paths = _scope_paths(_experiment_text(flat_params=True,
                                          flat_block_rows=8))
    assert any("fedzo.query" in p for p in paths)
    assert any("fedzo.forward" in p for p in paths)
    for p in paths:
        if "fedzo.query" in p:
            assert "fedzo.local" in p
        # the forward appears only inside a query
        if "fedzo.forward" in p:
            assert "fedzo.query" in p, p
        # the top-level layers never nest in one another
        assert len(p - {"fedzo.query", "fedzo.forward"}) <= 1, p


def test_sharded_round_scopes():
    store, p0 = _setup(), _params()
    cfg = _cfg(flat_params=True, flat_block_rows=8, aircomp=True,
               snr_db=10.0)
    rf = sim.make_sharded_round(softmax_loss, cfg, sim.make_clients_mesh())
    batches = sim.sample_batches(store, jnp.arange(2), jax.random.key(7),
                                 cfg.local_iters, cfg.b1)
    rngs = jax.random.split(jax.random.key(1), 2)
    text = jax.jit(lambda p, b, r, c: rf(
        softmax_loss, p, b, r, cfg, channel_rng=c)).lower(
            p0, batches, rngs, jax.random.key(2)).compile().as_text()
    seen = set().union(*_scope_paths(text))
    assert seen == {"fedzo.local", "fedzo.query", "fedzo.forward",
                    "fedzo.aggregate"}


def test_scope_refuses_unregistered_name():
    with pytest.raises(ValueError, match="unregistered scope"):
        obs.scope("fedzo.server")
    with obs.scope("fedzo.local"):
        pass


# ---------------------------------------------------------------------------
# comms ledger


def test_wire_bytes_model_matches_measured_message():
    from repro.core import seedcomm
    cfg = _cfg(local_iters=5, b2=20)
    msg = seedcomm.compress(jax.random.key(0),
                            jnp.zeros((5, 20), jnp.float32), cfg)
    assert seedcomm.wire_bytes_model(cfg) == seedcomm.wire_bytes(msg)


def test_ledger_columns_deterministic_ring_vs_full():
    store, cfg, p0 = _setup(), _cfg(), _params()
    rounds = 8
    full = engine.run_experiment(softmax_loss, p0, store, cfg, rounds,
                                 donate=False)
    ringed = engine.run_experiment(softmax_loss, p0, store, cfg, rounds,
                                   donate=False, ring_size=3)
    h_full = {r["round"]: r for r in engine.history(full)}
    for row in engine.history(ringed):
        assert row == h_full[row["round"]]
    # cumulative totals are (t+1)·per-round — a pure function of t
    led = full.ledger
    for t, row in sorted(h_full.items()):
        assert row["wire_bytes"] == led.round_uplink_bytes()
        assert row["wire_bytes_total"] == (t + 1) * led.round_uplink_bytes()
        assert row["downlink_bytes_total"] == \
            (t + 1) * led.round_downlink_bytes()
        assert row["compression_ratio"] == led.compression_ratio()


def test_ledger_seed_mode_and_effective_bytes():
    from repro.core import seedcomm
    from repro.utils.tree import tree_bytes
    cfg = _cfg(delta_compression="seed")
    p0 = _params()
    led = obs.CommsLedger.from_run(cfg, p0)
    assert led.mode == "seed"
    assert led.uplink_client_bytes == seedcomm.wire_bytes_model(cfg)
    assert led.dense_client_bytes == tree_bytes(p0)
    assert led.compression_ratio() > 1.0
    rows = [{"round": 0, "m_effective": 1.0},
            {"round": 1, "event": "rollback"}]
    led.annotate(rows)
    assert rows[0]["wire_bytes_effective"] == led.uplink_client_bytes
    assert "wire_bytes" not in rows[1]         # event rows pass untouched


# ---------------------------------------------------------------------------
# FedServer integration


def test_fedserver_round_ms_and_ledger_parity():
    from repro.fed.server import FedServer
    store, cfg, p0 = _setup(), _cfg(), _params()
    x, y = make_classification(320, 12, 3, seed=0)
    clients = noniid_shards(x, y, 4)
    host = FedServer(softmax_loss, p0, clients, cfg, store=store)
    for t in range(3):
        host.run_round(t)
    tr = obs.Tracer()
    scanned = FedServer(softmax_loss, p0, clients, cfg, store=store,
                        tracer=tr)
    scanned.run(3)
    assert len(tr.named("compile")) == 1 and len(tr.named("execute")) == 1
    for hrow, srow in zip(host.history, scanned.history):
        assert hrow["round"] == srow["round"]
        # host rows carry wall-clock; both drivers agree on the byte model
        assert hrow["round_ms"] > 0
        for k in ("wire_bytes", "wire_bytes_total", "downlink_bytes_total",
                  "dense_bytes", "compression_ratio"):
            assert hrow[k] == srow[k], k


# ---------------------------------------------------------------------------
# sweep tracer


def test_sweep_tracer_one_compile_per_static_group():
    from repro.sim.sweep import run_sweep, scenario_grid
    store, cfg, p0 = _setup(), _cfg(), _params()
    scenarios = scenario_grid(local_iters=(1, 2), lr=(1e-2, 5e-3))
    tr = obs.Tracer()
    recs = run_sweep(softmax_loss, p0, store, cfg, scenarios, 3,
                     tracer=tr)
    assert len(recs) == 4
    # 2 static groups (local_iters) × vmapped lr axis
    assert len(tr.named("compile")) == 2
    assert len(tr.named("execute")) == 2


@pytest.mark.parametrize("plan,want", [
    ({"flat_params": True},
     {"d": 39, "n_pad": 1024, "block_rows": 8, "directions_per_iterate": 7}),
    ({"flat_params": True, "flat_block_rows": 16},
     {"d": 39, "n_pad": 2048, "block_rows": 16,
      "directions_per_iterate": 7}),
    ({"batch_directions": True, "direction_conv": "block"},
     {"d": 39, "n_pad": 128, "block_rows": None,
      "directions_per_iterate": 2}),
    ({}, None),
], ids=["flat_derived", "flat_explicit", "wide", "pytree"])
def test_manifest_records_flat_geometry(tmp_path, plan, want):
    """The manifest says which flat buffer the kernels ran on: d, n_pad
    and block_rows (the pad ratio is n_pad / d), and the direction vectors
    a client generates per iterate (b2 = 2: 2 for the norms, 4 in the
    walk, 1 closing; the wide block 2); no block on the wide path without
    AirComp, which runs no kernel; nothing on the pytree path."""
    path = os.path.join(tmp_path, "rows.jsonl")
    with obs.JsonlSink(path) as sink:
        res = engine.run_experiment(softmax_loss, _params(), _setup(),
                                    _cfg(**plan), 1, sink=sink)
    man = obs.read_manifest(f"{path}.manifest.json")
    assert man.get("flat_geometry") == want
    assert res.manifest.get("flat_geometry") == want


def test_manifest_roundtrip(tmp_path):
    cfg = _cfg()
    led = obs.CommsLedger.from_run(cfg, _params())
    man = obs.build_manifest(cfg, strategy="fedzo", rounds=5, n_clients=4,
                             ledger=led,
                             faults=sim.FaultModel(p_fail=0.1,
                                                   p_recover=0.5),
                             events=[{"round": 2, "event": "rollback"}])
    path = obs.write_manifest(str(tmp_path), man)
    back = obs.read_manifest(path)
    assert back["config_hash"] == man["config_hash"]
    assert back["faults"]["stationary_up"] == pytest.approx(0.5 / 0.6)
    assert back["events"][0]["event"] == "rollback"
    assert back["topology"]["device_count"] >= 1
    assert "git_sha" in back
