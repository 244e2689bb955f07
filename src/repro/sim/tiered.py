"""Tiered client store: host-resident populations behind a cohort stream
(DESIGN.md §15).

The device-resident ``ClientStore`` caps the federation at device memory
and pads every client to the global max row count — fatal for the paper's
own regime, where only M of N clients matter per round and N is 10⁵–10⁶.
This module flips the storage/engine boundary: the population lives on the
HOST and only the sampled cohort (plus one prefetch buffer) ever touches
the device.

- ``HostStore`` — all N clients in host numpy (optionally memory-mapped
  ``.npy``) arrays, grouped into K **bucketed padding groups**: clients are
  binned by row count at size quantiles and each bucket is stacked at its
  OWN capacity, so pad waste is per-bucket, not global, and the engine
  compiles one program per bucket shape instead of one per round.
- ``CohortStream`` — replays the engine's participation key chain ON THE
  HOST: the same ``split(key, 5)`` (6 with faults) and the same
  ``sample_participants`` permutation the compiled round would draw, so
  the stream knows round t's M-cohort before the device reaches round t
  (bit-identical by construction; pinned by tests/test_tiered.py). Fault
  runs also host-replay the [N] Gilbert–Elliott chain via
  ``FaultModel.advance`` and stream only the [M] availability slice.
- ``run_tiered_experiment`` — the driver: double-buffered async staging
  (the next segment's ``jax.device_put`` overlaps the compiled current
  segment), segmented scans through ``engine.stream_core`` (the PR 6
  t0/total-rounds machinery, so chunked ≡ single-shot bitwise), durable
  checkpoints and divergence rollback matching the resident runner, and a
  prefetch-stall ledger for sim_bench.

[N]-sized carry state never enters the trace: the fault chain, the
wireless-scenario chain (``cfg.channel_model`` — host-replayed wholesale,
only the [M] realized fading + transmit mask is staged), and the stateful
strategies' per-client masters ({"client": [N, ...]}) are host-resident;
each segment slices the cohort's [M] rows in and scatters the returned
rows back. Snapshots keep the SAME npz leaf layout as the resident
engine's (params/momentum/key/fstate/cstate/zstate/ring/ebuf), so a
tiered run can resume a resident run's checkpoint and vice versa.

The central acceptance proof (tests/test_tiered.py): a ``HostStore`` run
is bitwise-identical to the ``ClientStore`` run on the same config —
including under faults, FedDyn/SCAFFOLD state, chunking, and
SIGKILL-and-resume — because every traced value is derived identically
and the host replica consumes exactly the key streams the trace leaves
unconsumed.
"""
from __future__ import annotations

import dataclasses
import json
import os
import time
from concurrent.futures import ThreadPoolExecutor
from contextlib import nullcontext
from dataclasses import dataclass
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import FedZOConfig
from repro.core import strategy as strategy_mod
from repro.core.fedzo import flat_layout
from repro.obs import manifest as obs_manifest
from repro.obs.ledger import CommsLedger
from repro.obs.taps import RoundTap
from repro.sim import channel as channel_lib
from repro.sim import engine
from repro.sim.faults import DivergenceError, FaultModel
from repro.sim.store import (ClientStore, CohortBatch, build_store,
                             client_sizes, sample_participants, stack_padded)
from repro.utils.tree import tree_zeros_like


# -- bucketed host population -------------------------------------------------

@dataclass
class Bucket:
    """One padding group: the clients whose row counts fall at or under
    this bucket's capacity (and over the previous bucket's), stacked
    [n_b, cap, ...] at the bucket's OWN cap."""
    ids: np.ndarray   # [n_b] int64 global client ids, ascending
    cap: int          # padded row capacity of this bucket
    data: Any         # pytree, leaves [n_b, cap, ...] host (maybe mmap)


def bucket_caps(sizes, n_buckets: int) -> list:
    """Deterministic bucket capacities: the size quantiles of the
    population (method="higher", so every cap is an actual client size and
    the last cap is the max), deduplicated ascending. Uniform populations
    collapse to one bucket."""
    qs = np.quantile(np.asarray(sizes),
                     np.linspace(0.0, 1.0, int(n_buckets) + 1)[1:],
                     method="higher")
    return sorted({int(q) for q in qs})


@dataclass
class HostStore:
    """All N clients host-resident in K bucketed padding groups, plus the
    index maps the cohort stream needs: ``sizes`` [N] true row counts,
    ``bucket_of`` [N] bucket index, ``row_of`` [N] row within the bucket."""
    buckets: list
    sizes: np.ndarray
    bucket_of: np.ndarray
    row_of: np.ndarray

    @property
    def n_clients(self) -> int:
        return int(self.sizes.shape[0])

    @property
    def n_buckets(self) -> int:
        return len(self.buckets)

    @property
    def capacity(self) -> int:
        return max(b.cap for b in self.buckets)

    @property
    def nbytes(self) -> int:
        """Host bytes of the bucketed population (data leaves only)."""
        return int(sum(l.nbytes for b in self.buckets
                       for l in jax.tree.leaves(b.data)))

    def client(self, i: int):
        """Client i's UNPADDED rows (host views — no copy off mmap)."""
        b = self.buckets[int(self.bucket_of[i])]
        r, n = int(self.row_of[i]), int(self.sizes[i])
        return jax.tree.map(lambda l: l[r, :n], b.data)

    # -- staging -------------------------------------------------------------
    def stage(self, idx_rounds) -> tuple:
        """Assemble the host-side cohort stream for a segment:
        ``idx_rounds`` [S, M] client ids -> (data pytree with leaves
        [S, M, cap, ...], sizes [S, M] int32, meta). ``cap`` is the max
        bucket capacity present in the segment, so the staged buffer is as
        small as the sampled cohorts allow while keeping ONE jit shape per
        (segment length, bucket cap). ``meta`` reports the cap, per-round
        dominating ``bucket_ids`` [S], and staged byte counts."""
        idx = np.asarray(idx_rounds, np.int64)
        s, m = idx.shape
        b_of = self.bucket_of[idx]                       # [S, M]
        rows = self.row_of[idx]                          # [S, M]
        present = np.unique(b_of)
        cap = max(self.buckets[int(b)].cap for b in present)
        treedef = jax.tree.structure(self.buckets[0].data)
        bleaves = [jax.tree.leaves(b.data) for b in self.buckets]
        out_leaves, nbytes = [], 0
        for j in range(treedef.num_leaves):
            head = bleaves[int(present[0])][j]
            out = np.zeros((s, m, cap) + head.shape[2:], head.dtype)
            for b in present:
                sel = np.nonzero(b_of == b)
                out[sel[0], sel[1], :self.buckets[int(b)].cap] = \
                    bleaves[int(b)][j][rows[sel]]
            nbytes += out.nbytes
            out_leaves.append(out)
        data = jax.tree.unflatten(treedef, out_leaves)
        sizes = self.sizes[idx].astype(np.int32)
        nbytes += sizes.nbytes
        meta = {"cap": int(cap),
                "bucket_ids": b_of.max(axis=1),
                "bytes": int(nbytes),
                "round_bytes": int(nbytes // max(1, s))}
        return data, sizes, meta

    def cohort_struct(self, m: int, *, with_avail: bool,
                      with_channel: bool = False) -> CohortBatch:
        """A ``ShapeDtypeStruct`` CohortBatch at the max capacity — the
        ``jax.eval_shape`` input for sizing the metrics ring."""
        cap = self.capacity
        data = jax.tree.map(
            lambda l: jax.ShapeDtypeStruct((m, cap) + tuple(l.shape[2:]),
                                           l.dtype),
            self.buckets[0].data)
        return CohortBatch(
            data=data, sizes=jax.ShapeDtypeStruct((m,), jnp.int32),
            avail=(jax.ShapeDtypeStruct((m,), jnp.bool_)
                   if with_avail else None),
            chan_h=(jax.ShapeDtypeStruct((m,), jnp.complex64)
                    if with_channel else None),
            chan_mask=(jax.ShapeDtypeStruct((m,), jnp.bool_)
                       if with_channel else None))

    # -- tier conversion -----------------------------------------------------
    def to_resident(self) -> ClientStore:
        """Materialize the device-resident tier: bit-identical to
        ``build_store`` over the same clients (each bucket's zero-padded
        rows land in the zero-initialized global-cap buffer, so the pad
        regions agree exactly)."""
        cap = int(self.sizes.max())
        n = self.n_clients
        treedef = jax.tree.structure(self.buckets[0].data)
        bleaves = [jax.tree.leaves(b.data) for b in self.buckets]
        out_leaves = []
        for j in range(treedef.num_leaves):
            head = bleaves[0][j]
            out = np.zeros((n, cap) + head.shape[2:], head.dtype)
            for bi, b in enumerate(self.buckets):
                out[b.ids, :b.cap] = bleaves[bi][j]
            out_leaves.append(jax.device_put(out))
        return ClientStore(data=jax.tree.unflatten(treedef, out_leaves),
                           sizes=jnp.asarray(self.sizes, jnp.int32))

    # -- durability ----------------------------------------------------------
    def save(self, path: str) -> str:
        """Persist the bucketed population as one ``.npy`` per leaf (the
        layout ``load(..., mmap=True)`` memory-maps) plus index arrays and
        a JSON manifest. Client pytrees must be (nested) dicts — the
        repo's client dataset format."""
        os.makedirs(path, exist_ok=True)
        np.save(os.path.join(path, "sizes.npy"), self.sizes)
        np.save(os.path.join(path, "bucket_of.npy"), self.bucket_of)
        np.save(os.path.join(path, "row_of.npy"), self.row_of)
        names = _leaf_names(self.buckets[0].data)
        for bi, b in enumerate(self.buckets):
            np.save(os.path.join(path, f"bucket{bi}_ids.npy"), b.ids)
            for name, leaf in zip(names, jax.tree.leaves(b.data)):
                np.save(os.path.join(path, f"bucket{bi}__{name}.npy"),
                        np.asarray(leaf))
        with open(os.path.join(path, "hoststore.json"), "w") as f:
            json.dump({"version": 1, "leaves": names,
                       "caps": [b.cap for b in self.buckets]}, f, indent=1)
        return path

    @classmethod
    def load(cls, path: str, *, mmap: bool = True) -> "HostStore":
        """Reopen a saved population. ``mmap=True`` memory-maps every data
        leaf, so a load costs index arrays only and ``stage()`` reads just
        the sampled cohorts' rows off disk — populations far beyond host
        RAM stay usable."""
        with open(os.path.join(path, "hoststore.json")) as f:
            man = json.load(f)
        mode = "r" if mmap else None
        buckets = []
        for bi, cap in enumerate(man["caps"]):
            ids = np.load(os.path.join(path, f"bucket{bi}_ids.npy"))
            leaves = [np.load(os.path.join(path, f"bucket{bi}__{n}.npy"),
                              mmap_mode=mode) for n in man["leaves"]]
            buckets.append(Bucket(ids=ids, cap=int(cap),
                                  data=_nest_leaves(man["leaves"], leaves)))
        return cls(buckets=buckets,
                   sizes=np.load(os.path.join(path, "sizes.npy")),
                   bucket_of=np.load(os.path.join(path, "bucket_of.npy")),
                   row_of=np.load(os.path.join(path, "row_of.npy")))


def _leaf_names(tree) -> list:
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    names = []
    for kp, _v in flat:
        parts = []
        for k in kp:
            if not isinstance(k, jax.tree_util.DictKey):
                raise ValueError(
                    "HostStore.save supports dict-structured client "
                    f"pytrees; got key {k!r}")
            parts.append(str(k.key))
        names.append("/".join(parts))
    return names


def _nest_leaves(names: list, leaves: list):
    out: dict = {}
    for name, leaf in zip(names, leaves):
        node, parts = out, name.split("/")
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = leaf
    return out


def build_host_store(clients, n_buckets: int = 4) -> HostStore:
    """Bucket a list of per-client dataset pytrees into a ``HostStore``.

    Capacities come from ``bucket_caps`` (size quantiles); each client
    lands in the smallest bucket whose cap covers its row count, keeping
    its rows exactly once (the partition invariants the hypothesis test
    pins). Stacking reuses ``stack_padded`` — one preallocated buffer per
    (bucket, leaf), never transient padded copies."""
    sizes = np.asarray(client_sizes(clients), np.int64)
    caps = bucket_caps(sizes, n_buckets)
    assign = np.searchsorted(caps, sizes, side="left")
    n = sizes.shape[0]
    bucket_of = np.zeros(n, np.int64)
    row_of = np.zeros(n, np.int64)
    buckets = []
    for cap in caps:
        ids = np.nonzero(assign == caps.index(cap))[0]
        if ids.size == 0:      # dedup can orphan a quantile; drop it
            continue
        bucket_of[ids] = len(buckets)
        row_of[ids] = np.arange(ids.size)
        data = jax.tree.map(lambda *ls, c=cap: stack_padded(ls, c),
                            *[clients[int(i)] for i in ids])
        buckets.append(Bucket(ids=ids, cap=int(cap), data=data))
    return HostStore(buckets=buckets, sizes=sizes, bucket_of=bucket_of,
                     row_of=row_of)


def resolve_store(store, *, tier: str = "auto"):
    """The one seam through which drivers accept either store tier.

    ``tier="resident"`` always returns a device-resident ``ClientStore``
    (a ``HostStore`` is materialized via ``to_resident()``, bit-identical
    to ``build_store`` on the same clients — so ``FedServer``, ``sweep``,
    and the sharded round run unchanged on either input). ``tier="host"``
    builds/keeps the host tier. ``tier="auto"`` keeps whatever tier was
    passed; a plain list of client datasets builds the resident tier."""
    if isinstance(store, ClientStore):
        return store
    if isinstance(store, HostStore):
        return store.to_resident() if tier == "resident" else store
    if isinstance(store, (list, tuple)):
        return (build_host_store(list(store)) if tier == "host"
                else build_store(list(store)))
    raise TypeError(f"not a client store or client list: "
                    f"{type(store).__name__}")


# -- host key-chain replay ----------------------------------------------------

class CohortStream:
    """Host replica of the engine's per-round key chain.

    Each ``next_round()`` performs the EXACT splits the compiled round
    performs on its carry key — ``engine.split_round_keys``, the shared
    single source of truth — and consumes the streams the trace leaves
    unconsumed: ``k_part`` draws the participation permutation
    (``sample_participants``, same Threefry path, eager instead of traced
    — bit-identical), on fault runs the availability substream of
    ``k_fault`` advances the [N] chain (``FaultModel.advance``), and on
    wireless-scenario runs ``k_chanm`` advances the WHOLE channel chain
    (``ChannelModel.step`` is pure in (key, state, idx) with no delta
    dependence, so the host replay — fading, scheduling, battery debits —
    is the in-carry derivation, not an approximation of it). The stream's
    key therefore stays in lockstep with the device carry key round for
    round (pinned by test), which is what lets staging run arbitrarily
    far ahead of the device."""

    def __init__(self, store: HostStore, cfg: FedZOConfig, key, *,
                 faults: Optional[FaultModel] = None, fstate=None,
                 cstate=None):
        self.store, self.cfg = store, cfg
        self.key = key
        self.faults = faults
        self.fstate = fstate
        self.channel = cfg.channel_model
        self.cstate = cstate

    def next_round(self) -> tuple:
        """Advance one round: -> (idx [M] int64, avail [M] bool | None,
        chan_h [M] complex64 | None, chan_mask [M] bool | None)."""
        self.key, k_part, _kb, _kz, _kc, k_fault, k_chanm = \
            engine.split_round_keys(self.key,
                                    faults=self.faults is not None,
                                    channel=self.channel is not None)
        idx = np.asarray(sample_participants(
            k_part, self.store.n_clients, self.cfg.n_participating),
            np.int64)
        avail = None
        if self.faults is not None:
            k_avail = jax.random.split(k_fault, 3)[0]
            self.fstate = self.faults.advance(k_avail, self.fstate)
            avail = np.asarray(self.fstate)[idx]
        chan_h = chan_mask = None
        if self.channel is not None:
            self.cstate, rchan = self.channel.step(
                k_chanm, self.cstate, jnp.asarray(idx),
                h_min=self.cfg.h_min, schedule=self.cfg.channel_schedule)
            chan_h = np.asarray(rchan.h)
            chan_mask = np.asarray(rchan.mask)
        return idx, avail, chan_h, chan_mask

    def plan(self, n: int) -> tuple:
        """Replay ``n`` rounds ahead: -> (idx [n, M], avail [n, M]|None,
        chan_h [n, M]|None, chan_mask [n, M]|None)."""
        drawn = [self.next_round() for _ in range(n)]
        idx = np.stack([d[0] for d in drawn])
        avail = (np.stack([d[1] for d in drawn])
                 if self.faults is not None else None)
        chan_h = (np.stack([d[2] for d in drawn])
                  if self.channel is not None else None)
        chan_mask = (np.stack([d[3] for d in drawn])
                     if self.channel is not None else None)
        return idx, avail, chan_h, chan_mask


class _Ready:
    """Future-shaped wrapper for the prefetch-off path."""

    def __init__(self, value):
        self._value = value

    def result(self):
        return self._value


# -- the tiered experiment runner ---------------------------------------------

def run_tiered_experiment(loss_fn, params, store: HostStore,
                          cfg: FedZOConfig, rounds: int, *,
                          algo: Optional[str] = None, strategy=None,
                          eval_fn=None, eval_every: int = 0,
                          ring_size: int = 0, key=None, momentum=None,
                          round_fn=None,
                          faults: Optional[FaultModel] = None,
                          donate: bool = True, checkpoint_every: int = 0,
                          checkpoint_dir=None, resume: bool = False,
                          max_segments=None, segment_callback=None,
                          max_retries: int = 3, lr_backoff: float = 0.5,
                          sink=None, tap_every: Optional[int] = None,
                          tracer=None, stream_segment: int = 8,
                          prefetch: bool = True) -> engine.ExperimentResult:
    """``run_experiment`` over a host-resident population.

    Same contract and (bitwise) the same trajectory as the resident
    runner on the equivalent ``ClientStore`` — checkpointing, divergence
    rollback with lr backoff, taps, tracer spans, ledger and manifest all
    included — but the device only ever holds the in-flight segment's
    cohorts plus ONE prefetch buffer:

    - the ``CohortStream`` plans ``stream_segment`` rounds ahead on the
      main thread (key-chain replay), a single worker thread stages and
      ``jax.device_put``s the next segment while the device runs the
      current compiled segment (double buffering; ``prefetch=False``
      serializes, for measurement);
    - stateful strategies force ``stream_segment=1``: their [N] client
      master lives in host numpy, the cohort's [M] rows are sliced in and
      scattered back every round (overlapping cohorts would read stale
      state otherwise). The fault chain needs no such clamp — the stream
      replays it forward;
    - ``result.staging`` records each round's dominating bucket id and
      staged bytes (merged into ``history()`` rows by the ledger), and
      ``result.prefetch`` the stall accounting sim_bench reports
      (``stall_pct`` = time the main loop blocked waiting on staging /
      total wall time, cold-start segment excluded).
    """
    from repro.checkpoint import checkpoint as ckpt

    strat = strategy_mod.resolve(strategy, algo, cfg)
    strat.validate(cfg)
    if key is None:
        key = engine.experiment_key(cfg)
    if momentum is None and strat.has_momentum(cfg):
        momentum = tree_zeros_like(params)
    n_clients = store.n_clients
    m = cfg.n_participating
    do_eval = eval_fn is not None and eval_every > 0
    tap = None
    if tap_every is not None:
        if sink is None:
            raise ValueError("tap_every=k needs a sink= to stream into")
        tap = RoundTap(sink, tap_every)
    channel = cfg.channel_model
    ledger = CommsLedger.from_run(cfg, params, channel=channel)
    flat = flat_layout(params, cfg)
    if checkpoint_every > 0 and checkpoint_dir is None:
        raise ValueError("checkpoint_every > 0 requires checkpoint_dir")

    # host-resident [N] halves of the carry
    fstate = faults.init_state(n_clients) if faults is not None else None
    # wireless-scenario chain (sim/channel.py): host-resident like fstate —
    # the stream replays it and stages only the [M] realization per round
    cstate = (channel.init_state(n_clients, channel_lib.init_key(key))
              if channel is not None else None)
    z_template = strat.init_state(params, cfg, 1)
    stateful = z_template is not None
    if stateful:
        client_master = jax.tree.map(
            lambda l: np.zeros((n_clients,) + tuple(l.shape[1:]),
                               np.asarray(l).dtype), z_template["client"])
        z_server = jax.tree.map(jnp.asarray, z_template["server"])
        seg_len = 1
    else:
        client_master, z_server = None, None
        seg_len = max(1, int(stream_segment))

    ring_alloc = min(rounds, ring_size) if ring_size else rounds
    n_evals = (rounds + eval_every - 1) // eval_every if do_eval else 0
    step = engine.make_cohort_round_step(loss_fn, cfg, strategy=strat,
                                         round_fn=round_fn, faults=faults)
    zc_struct = None
    if stateful:
        zc_struct = {"client": jax.tree.map(
            lambda l: jax.ShapeDtypeStruct((m,) + tuple(l.shape[1:]),
                                           l.dtype), z_template["client"]),
            "server": z_server}
    ring, ebuf = engine._zero_buffers(
        step, (params, momentum, key, zc_struct),
        store.cohort_struct(m, with_avail=faults is not None,
                            with_channel=channel is not None),
        eval_fn=eval_fn, params=params, ring_alloc=ring_alloc,
        n_evals=n_evals)

    t, events, cur_lr = 0, [], cfg.lr
    orig_hash = ckpt.config_hash(cfg)

    def pack_state():
        # SAME leaf layout as the resident engine's _carry_to_state: the
        # host-resident halves slot into the fstate/cstate/zstate keys, so
        # tiered and resident snapshots of one run interchange
        return {"params": params, "momentum": momentum,
                "key": jax.random.key_data(key), "fstate": fstate,
                "cstate": cstate,
                "zstate": ({"client": client_master, "server": z_server}
                           if stateful else None),
                "ring": ring, "ebuf": ebuf}

    if checkpoint_every > 0 and resume:
        snap = ckpt.latest_run_state(checkpoint_dir)
        if snap is not None:
            state_r, meta = ckpt.restore_run_state(snap, pack_state())
            if meta.get("config_hash") not in (None, orig_hash):
                import warnings
                warnings.warn(
                    f"resuming from a snapshot of a DIFFERENT config "
                    f"(hash {meta.get('config_hash')} != {orig_hash}) — "
                    f"the continued trajectory will not match either run")
            t = int(meta["round"])
            events = list(meta.get("events", []))
            cur_lr = float(meta.get("lr", cfg.lr))
            params, momentum, key, fstate, cstate, client_master, \
                z_server, ring, ebuf = _unpack_state(state_r, cfg, stateful)

    stream = CohortStream(store, cfg, key, faults=faults, fstate=fstate,
                          cstate=cstate)

    def checkpoint_meta():
        return {"round": t, "rounds_total": rounds, "algo": strat.name,
                "strategy": strat.name, "config_hash": orig_hash,
                "lr": cur_lr, "events": events}

    def tiered_block():
        return {"tiered": {"n_buckets": store.n_buckets,
                           "stream_segment": seg_len,
                           "host_bytes": store.nbytes,
                           "prefetch": bool(prefetch)}}

    def write_run_manifest():
        man = obs_manifest.build_manifest(
            cfg, strategy=strat.name, rounds=rounds, n_clients=n_clients,
            ledger=ledger, faults=faults, channel=channel, events=events,
            flat=flat,
            extra={"checkpoint_every": checkpoint_every, "lr": cur_lr,
                   "rounds_done": t,
                   "tap_every": tap.every if tap is not None else None,
                   **tiered_block()})
        obs_manifest.write_manifest(checkpoint_dir, man)
        return man

    if checkpoint_every > 0:
        if t == 0:
            ckpt.save_run_state(checkpoint_dir,
                                jax.device_get(pack_state()),
                                round_idx=0, meta=checkpoint_meta())
        write_run_manifest()

    seg_fns: dict = {}

    def segment_fn():
        if cur_lr not in seg_fns:
            run_cfg = (cfg if cur_lr == cfg.lr
                       else dataclasses.replace(cfg, lr=cur_lr))

            def fn(params, momentum, key, zstate, ring, ebuf, t0, xs):
                return engine.stream_core(
                    loss_fn, params, run_cfg, key, momentum, strategy=strat,
                    zstate=zstate, xs=xs, t0=t0, total_rounds=rounds,
                    ring=ring, ebuf=ebuf, eval_fn=eval_fn,
                    eval_every=eval_every, ring_size=ring_size,
                    round_fn=round_fn, faults=faults, tap=tap)

            seg_fns[cur_lr] = jax.jit(
                fn, donate_argnums=(0, 1, 2, 3, 4, 5) if donate else ())
        return seg_fns[cur_lr]

    def stage_put(idx, avail, chan_h, chan_mask):
        data, sizes, meta = store.stage(idx)
        xb = CohortBatch(data=data, sizes=sizes, avail=avail,
                         chan_h=chan_h, chan_mask=chan_mask)
        return jax.device_put(xb), meta

    pool = ThreadPoolExecutor(max_workers=1) if prefetch else None

    def submit(start):
        end = min(start + seg_len, rounds)
        if checkpoint_every > 0:
            end = min(end,
                      (start // checkpoint_every + 1) * checkpoint_every)
        idx, avail, chan_h, chan_mask = stream.plan(end - start)
        fut = (pool.submit(stage_put, idx, avail, chan_h, chan_mask)
               if pool is not None
               else _Ready(stage_put(idx, avail, chan_h, chan_mask)))
        # the chain state AS OF round `end` — stream.fstate/.cstate race
        # ahead with the prefetch, snapshots must not
        return fut, idx, end, stream.fstate, stream.cstate

    staging_rows: dict = {}
    prefetch_stats = {"stall_s": 0.0, "wall_s": 0.0, "stall_pct": 0.0,
                      "staged_bytes": 0, "host_bytes": store.nbytes,
                      "device_segment_bytes_max": 0,
                      "stream_segment": seg_len,
                      "n_buckets": store.n_buckets}
    retries, segments_done, last_ckpt = 0, 0, t
    cold = True
    wall0 = time.perf_counter()
    pending = submit(t)
    try:
        with (tracer.profile() if tracer is not None else nullcontext()):
            while t < rounds:
                fut, idx, end, seg_fstate, seg_cstate = pending
                w0 = time.perf_counter()
                with (tracer.span("tiered.stage_wait", t0=t)
                      if tracer is not None else nullcontext()):
                    xs, smeta = fut.result()
                waited = time.perf_counter() - w0
                if cold:
                    cold = False    # nothing to overlap the first wait with
                else:
                    prefetch_stats["stall_s"] += waited
                if end < rounds:
                    pending = submit(end)
                seg = end - t
                zc = ({"client": jax.tree.map(
                          lambda a: jnp.asarray(a[idx[0]]), client_master),
                       "server": z_server} if stateful else None)
                jitted = segment_fn()
                args = (params, momentum, key, zc, ring, ebuf,
                        jnp.int32(t), xs)
                if tracer is not None:
                    run = tracer.timed_compile(
                        ("tiered_segment", seg, smeta["cap"], stateful,
                         cur_lr, orig_hash), jitted, *args)
                    span = tracer.span("tiered_segment", t0=t, chunk=seg,
                                       bucket_cap=smeta["cap"])
                else:
                    run, span = jitted, nullcontext()
                with span:
                    out = run(*args)
                params, momentum, key, zc_out, ring, ebuf = out
                fstate = seg_fstate
                cstate = seg_cstate
                if stateful:
                    host_rows = jax.device_get(zc_out["client"])
                    jax.tree.map(lambda a, v: a.__setitem__(idx[0], v),
                                 client_master, host_rows)
                    z_server = zc_out["server"]
                for j in range(seg):
                    staging_rows[t + j] = {
                        "bucket_id": int(smeta["bucket_ids"][j]),
                        "staged_bytes": int(smeta["round_bytes"])}
                prefetch_stats["staged_bytes"] += int(smeta["bytes"])
                prefetch_stats["device_segment_bytes_max"] = max(
                    prefetch_stats["device_segment_bytes_max"],
                    int(smeta["bytes"]))
                t = end
                if checkpoint_every > 0 and \
                        (t % checkpoint_every == 0 or t >= rounds):
                    state = jax.device_get(pack_state())
                    if not engine._finite_state(state, range(last_ckpt, t),
                                                ring_alloc, eval_every,
                                                do_eval):
                        retries += 1
                        if retries > max_retries:
                            raise DivergenceError(t, max_retries, cur_lr)
                        cur_lr *= lr_backoff
                        events.append({"round": t, "event": "rollback",
                                       "from_round": last_ckpt,
                                       "retry": retries, "lr": cur_lr})
                        seg_fns.clear()   # backed-off lr is baked in
                        if tracer is not None:
                            tracer.invalidate_compiled()
                        snap = ckpt.latest_run_state(checkpoint_dir)
                        good, gm = ckpt.restore_run_state(snap, state)
                        params, momentum, key, fstate, cstate, \
                            client_master, z_server, ring, ebuf = \
                            _unpack_state(good, cfg, stateful)
                        t = int(gm["round"])
                        last_ckpt = t
                        stream = CohortStream(store, cfg, key,
                                              faults=faults, fstate=fstate,
                                              cstate=cstate)
                        pending = submit(t)
                        cold = True
                        continue
                    retries = 0
                    ckpt.save_run_state(checkpoint_dir, state, round_idx=t,
                                        meta=checkpoint_meta())
                    last_ckpt = t
                    segments_done += 1
                    if segment_callback is not None:
                        segment_callback(t, rounds)
                    if max_segments is not None and \
                            segments_done >= max_segments:
                        break
    finally:
        if pool is not None:
            pool.shutdown(wait=True, cancel_futures=True)

    jax.block_until_ready(jax.tree.leaves(params)[0])
    wall = time.perf_counter() - wall0
    prefetch_stats["wall_s"] = wall
    prefetch_stats["stall_pct"] = (100.0 * prefetch_stats["stall_s"] / wall
                                   if wall > 0 else 0.0)

    manifest = write_run_manifest() if checkpoint_every > 0 else None
    eval_rounds = np.arange(0, t, eval_every) if do_eval else np.arange(0)
    result = engine.ExperimentResult(
        params=params, momentum=momentum, key=key, metrics=ring,
        evals=ebuf, rounds=t, ring_size=ring_alloc,
        eval_rounds=eval_rounds,
        fault_state=(jnp.asarray(fstate) if faults is not None else None),
        channel_state=(jax.tree.map(jnp.asarray, cstate)
                       if channel is not None else None),
        events=list(events), strategy=strat.name,
        strategy_state=({"client": jax.tree.map(jnp.asarray, client_master),
                         "server": z_server} if stateful else None),
        ledger=ledger, manifest=manifest, staging=staging_rows,
        prefetch=prefetch_stats)
    sink_path = getattr(sink, "path", None)
    if sink_path:
        result.manifest = obs_manifest.build_manifest(
            cfg, strategy=strat.name, rounds=rounds, n_clients=n_clients,
            ledger=ledger, faults=faults, channel=channel,
            events=result.events, flat=flat,
            extra={**({"tap_every": tap.every} if tap is not None else {}),
                   **tiered_block()})
        obs_manifest.write_manifest(f"{sink_path}.manifest.json",
                                    result.manifest)
    return result


def _unpack_state(state: dict, cfg: FedZOConfig, stateful: bool) -> tuple:
    """Split a restored snapshot back into the tiered carry: device halves
    as jax arrays, host-resident halves as WRITABLE numpy (the [N] client
    master is scattered into in place every segment)."""
    key = jax.random.wrap_key_data(jnp.asarray(state["key"]),
                                   impl=cfg.prng_impl)
    params = jax.tree.map(jnp.asarray, state["params"])
    momentum = (None if state["momentum"] is None
                else jax.tree.map(jnp.asarray, state["momentum"]))
    fstate = (None if state["fstate"] is None
              else jnp.asarray(state["fstate"]))
    cstate = (None if state.get("cstate") is None
              else jax.tree.map(jnp.asarray, state["cstate"]))
    if stateful:
        client_master = jax.tree.map(
            lambda a: np.array(jax.device_get(a)), state["zstate"]["client"])
        z_server = jax.tree.map(jnp.asarray, state["zstate"]["server"])
    else:
        client_master, z_server = None, None
    ring = jax.tree.map(jnp.asarray, state["ring"])
    ebuf = jax.tree.map(jnp.asarray, state["ebuf"])
    return (params, momentum, key, fstate, cstate, client_master, z_server,
            ring, ebuf)
