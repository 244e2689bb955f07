"""Fully-jitted multi-round federation engine (DESIGN.md §9).

One compiled program runs an ENTIRE experiment: ``lax.scan`` over R
communication rounds, each round drawing its participants and minibatches
from the device-resident ``ClientStore`` (sim/store.py), running the
round of the resolved ``AlgoStrategy`` (core/strategy.py — FedZO, FedAvg,
ZO-FedProx, ZO-FedDyn, ZO-SCAFFOLD; momentum, strategy state, and channel
scheduling threaded through the carry), and writing its scalar metrics
into a fixed-shape ring buffer. Evaluation runs in-scan every k rounds
behind a ``lax.cond``. The host syncs exactly once, after all R rounds.

Key-chain protocol (shared with ``FedServer.run_round`` on the store path,
so R in-jit rounds bit-match R host-driven rounds):

    key, k_part, k_batch, k_zo, k_chan = split(key, 5)      # per round

``k_part`` draws the M-of-N participation permutation, ``k_batch`` the
local minibatches, ``k_zo`` the M per-client ZO keys, ``k_chan`` the
channel realization. The chain starts at ``key(cfg.seed, impl=
cfg.prng_impl)`` so a whole experiment is bit-reproducible from the config.
With a ``FaultModel`` attached the split widens to 6 and the extra
``k_fault`` stream drives the availability/straggler/corruption draws; a
``cfg.channel_model`` (sim/channel.py) widens it once more and the last
stream ``k_chanm`` advances the wireless-scenario chain (``split_round_
keys`` is the single source of truth). Runs without the optional
processes keep their exact narrower chains, so existing trajectories (and
the golden fixtures) are untouched. Strategies draw nothing of their own:
their state updates are deterministic functions of the round, so switching
strategy never perturbs the chain.

Donation: the jitted program donates params, momentum, key, and strategy
state, so at steady state the engine updates the model in place — no
per-round host↔device traffic and no double-buffered parameter copies.

Durability (DESIGN.md §12): ``run_experiment(..., checkpoint_every=k,
checkpoint_dir=...)`` runs the same scan in k-round segments, paying ONE
host sync + one atomic snapshot of the full carry (params, momentum, key,
fault state, strategy state, metrics ring, eval buffer, round index) per
segment. A run killed between segments resumes bit-exactly
(``resume=True``), and the per-segment sync doubles as the divergence
guard: a non-finite carry rolls back to the last good snapshot with lr
backoff, bounded by ``max_retries``.

Observability (DESIGN.md §14): ``run_experiment(..., sink=obs.JsonlSink(p),
tap_every=k)`` streams every k-th round's metrics to the sink LIVE from
inside the compiled scan (an unordered ``io_callback`` behind a
``lax.cond``, so non-tap rounds pay nothing); the default ``tap_every=None``
never enters the trace and keeps the one-host-sync property bit-identical
to the golden fixtures. ``tracer=obs.Tracer(...)`` records nested
compile/execute (or per-segment) spans — compile reported exactly once per
static shape — and optionally drops a ``jax.profiler`` trace. Every result
carries an ``obs.CommsLedger`` (``history()`` rows gain per-round
wire/dense bytes and cumulative uplink/downlink totals), and runs with a
checkpoint dir or a file-backed sink emit a run manifest beside their
artifacts.
"""
from __future__ import annotations

import dataclasses
from contextlib import nullcontext
from dataclasses import dataclass, field
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import io_callback

from repro.configs.base import FedZOConfig
from repro.core import aircomp
from repro.core import strategy as strategy_mod
from repro.core.fedzo import flat_layout
from repro.core.strategy import _static_positive  # noqa: F401  (re-export)
from repro.obs import manifest as obs_manifest
from repro.obs.ledger import CommsLedger
from repro.obs.taps import RoundTap
from repro.obs.trace import scope
from repro.sim import channel as channel_lib
from repro.sim.channel import RoundChannel
from repro.sim.faults import DivergenceError, FaultModel
from repro.sim.store import (ClientStore, CohortBatch, sample_batches,
                             sample_cohort_batches, sample_participants)
from repro.utils.tree import tree_zeros_like


def round_keys(key):
    """(next_carry_key, k_participation, k_batches, k_zo, k_channel)."""
    ks = jax.random.split(key, 5)
    return ks[0], ks[1], ks[2], ks[3], ks[4]


def split_round_keys(key, *, faults: bool = False, channel: bool = False):
    """The per-round key split, widened by the optional extra processes:
    ``(key', k_part, k_batch, k_zo, k_chan, k_fault, k_chanm)`` with
    ``k_fault`` / ``k_chanm`` None when faults / the channel model are off.

    THE single source of truth for the widening order (fault stream first,
    channel-chain stream last), shared by the resident step, the cohort
    step, and the tiered ``CohortStream``'s host replay. A run without the
    optional processes keeps the exact narrower split — base runs the
    5-way ``round_keys`` chain, faults-only runs the historical 6-way one —
    so attaching a ``ChannelModel`` to a config never perturbs existing
    trajectories (the golden fixtures pin this)."""
    n = 5 + int(faults) + int(channel)
    ks = jax.random.split(key, n)
    k_fault = ks[5] if faults else None
    k_chanm = ks[5 + int(faults)] if channel else None
    return ks[0], ks[1], ks[2], ks[3], ks[4], k_fault, k_chanm


def experiment_key(cfg: FedZOConfig):
    """Round-0 carry key of an experiment: the one derivation both the
    engine and the FedServer store path start from."""
    return jax.random.key(cfg.seed, impl=cfg.prng_impl)


def _resolve(strategy, algo, cfg) -> strategy_mod.AlgoStrategy:
    return strategy_mod.resolve(strategy, algo, cfg)


def make_round_step(loss_fn, cfg: FedZOConfig, *, algo: Optional[str] = None,
                    strategy=None, round_fn=None,
                    faults: Optional[FaultModel] = None) -> Callable:
    """One full communication round as a pure function
    ``step((params, momentum, key, fstate, cstate, zstate), store) ->
    ((params', momentum', key', fstate', cstate', zstate'), metrics)``.

    THE round unit shared by the scan engine and by
    ``FedServer.run_round`` on the store path — sharing it is what makes
    the two trajectories bit-identical (under faults too: the fault draws
    hang off the same carried key chain). The algorithm comes from the
    strategy registry (``strategy=`` a name or ``AlgoStrategy``; the
    ``algo=`` string is a deprecated alias; default ``cfg.strategy``).
    ``round_fn`` optionally replaces ``fedzo.round_simulated`` with a
    signature-compatible deployment (the clients-axis shard_map round of
    sim/shard.py) — only for strategies without hooks. ``fstate`` is the
    fault carry (the [N] Gilbert–Elliott availability states), ``cstate``
    the wireless-scenario carry of ``cfg.channel_model`` (the [N] AR(1)
    fading chain + [N] batteries, sim/channel.py — its ``step`` realizes
    the round's ``RoundChannel`` and the transmit mask), ``zstate`` the
    strategy carry ({"client": [N, ...], "server": ...} pytree for the
    stateful strategies); all None when unused.
    """
    strat = _resolve(strategy, algo, cfg)
    strat.validate(cfg)
    if round_fn is not None and not strat.supports_round_fn:
        raise ValueError(
            f"strategy {strat.name!r} wraps the local phase with loss/state "
            f"hooks that a custom round_fn (the sharded round) cannot carry "
            f"— run it through the default fedzo round")
    weigh = cfg.weight_by_size
    channel = cfg.channel_model

    def step(state, store: ClientStore):
        params, momentum, key, fstate, cstate, zstate = state
        key, k_part, k_batch, k_zo, k_chan, k_fault, k_chanm = \
            split_round_keys(key, faults=faults is not None,
                             channel=channel is not None)
        with scope("fedzo.cohort"):
            idx = sample_participants(k_part, store.n_clients,
                                      cfg.n_participating)
            batches = sample_batches(store, idx, k_batch, cfg.local_iters,
                                     cfg.b1)
            # FedAvg-style n_i/n weights of the sampled clients (mean-1
            # normalized); only added to the round call when enabled so
            # custom round_fns without a weights kwarg keep working — the
            # per-round fault and channel realizations ride the same
            # pattern
            wkw = ({"weights": aircomp.size_weights(store.sizes[idx])}
                   if weigh else {})
            if faults is not None:
                fstate, inj = faults.step(k_fault, fstate, idx)
                wkw["faults"] = inj
            if channel is not None:
                cstate, wkw["channel"] = channel.step(
                    k_chanm, cstate, idx, h_min=cfg.h_min,
                    schedule=cfg.channel_schedule)
        params, metrics, momentum, zstate = strat.run_round(
            loss_fn, params, batches, k_zo, cfg, channel_rng=k_chan,
            momentum=momentum, zstate=zstate, idx=idx, round_fn=round_fn,
            **wkw)
        return (params, momentum, key, fstate, cstate, zstate), metrics

    return step


def make_cohort_round_step(loss_fn, cfg: FedZOConfig, *,
                           algo: Optional[str] = None, strategy=None,
                           round_fn=None,
                           faults: Optional[FaultModel] = None) -> Callable:
    """One communication round as a function of a STAGED cohort instead of
    a device-resident store: ``step((params, momentum, key, zstate),
    CohortBatch) -> ((params', momentum', key', zstate'), metrics)``.

    The tiered twin of ``make_round_step`` (DESIGN.md §15). Bit-equality
    with the resident round is by construction:

    - the round walks the SAME per-round key chain (5-way split, widened
      by faults / channel) but leaves ``k_part`` unconsumed — the host
      ``CohortStream`` already spent its replica choosing which clients
      were staged — and the chain depends only on the splits, never on
      consumption;
    - minibatches come from ``sample_cohort_batches`` over the staged
      rows and TRUE sizes, the same randint draws and exact gathers the
      resident ``sample_batches`` performs;
    - faults use ``FaultModel.realize`` on the host-replayed availability
      slice (``CohortBatch.avail``), splitting the same 3-way fault chain;
    - the wireless channel (``cfg.channel_model``) is host-replayed
      WHOLLY: the chain's ``step`` is pure in (key, state, idx), so the
      stream stages the realized cohort fading + transmit mask
      (``CohortBatch.chan_h`` / ``chan_mask``) and the in-trace round
      leaves ``k_chanm`` unconsumed like ``k_part``;
    - ``zstate`` is cohort-shaped ({"client": [M, ...], "server": ...})
      and ``idx = arange(M)``, so the stateful strategies' gather/scatter
      hooks run unmodified as identity permutations — the [N] master
      lives on the host and is sliced/scattered around the trace.
    """
    strat = _resolve(strategy, algo, cfg)
    strat.validate(cfg)
    if round_fn is not None and not strat.supports_round_fn:
        raise ValueError(
            f"strategy {strat.name!r} wraps the local phase with loss/state "
            f"hooks that a custom round_fn (the sharded round) cannot carry "
            f"— run it through the default fedzo round")
    weigh = cfg.weight_by_size
    channel = cfg.channel_model

    def step(state, cohort: CohortBatch):
        params, momentum, key, zstate = state
        key, k_part, k_batch, k_zo, k_chan, k_fault, k_chanm = \
            split_round_keys(key, faults=faults is not None,
                             channel=channel is not None)
        del k_part, k_chanm  # consumed host-side by the CohortStream replay
        with scope("fedzo.cohort"):
            batches = sample_cohort_batches(cohort.data, cohort.sizes,
                                            k_batch, cfg.local_iters, cfg.b1)
            # cohort.sizes IS store.sizes[idx] (staged by the stream), so
            # the weights match the resident round bit-for-bit
            wkw = ({"weights": aircomp.size_weights(cohort.sizes)}
                   if weigh else {})
            if faults is not None:
                wkw["faults"] = faults.realize(k_fault, cohort.avail)
            if channel is not None:
                wkw["channel"] = RoundChannel(model=channel, h=cohort.chan_h,
                                              mask=cohort.chan_mask)
            idx = jnp.arange(cohort.sizes.shape[0], dtype=jnp.int32)
        params, metrics, momentum, zstate = strat.run_round(
            loss_fn, params, batches, k_zo, cfg, channel_rng=k_chan,
            momentum=momentum, zstate=zstate, idx=idx, round_fn=round_fn,
            **wkw)
        return (params, momentum, key, zstate), metrics

    return step


@dataclass
class ExperimentResult:
    """Host-side container for one engine run. ``metrics`` holds the ring
    buffer (dict of [ring_size] arrays, slot = round % ring_size);
    ``evals`` the in-scan eval outputs (dict of [n_evals] arrays), one slot
    per eval round in ``eval_rounds``. ``fault_state`` carries the final
    [N] availability states when a ``FaultModel`` was attached;
    ``channel_state`` the final wireless-scenario carry (the [N] fading
    chain + [N] batteries) when ``cfg.channel_model`` is set; ``events``
    holds structured host-side rows (divergence rollbacks); ``strategy``
    the algorithm name and ``strategy_state`` its final carry (the stacked
    per-client controls/duals + server control for scaffold/feddyn).
    ``ledger`` is the run's ``obs.CommsLedger`` (``history()`` rows get the
    byte columns from it) and ``manifest`` the emitted run-manifest dict
    (None when the run had nowhere to write one). Tiered runs
    (sim/tiered.py) additionally fill ``staging`` (round -> {bucket_id,
    staged_bytes}, merged into ``history()`` rows) and ``prefetch`` (the
    stream's stall/byte accounting)."""
    params: Any
    momentum: Any
    key: Any
    metrics: dict
    evals: dict
    rounds: int
    ring_size: int
    eval_rounds: np.ndarray
    fault_state: Any = None
    channel_state: Any = None
    events: list = field(default_factory=list)
    strategy: str = "fedzo"
    strategy_state: Any = None
    ledger: Any = None
    manifest: Any = None
    staging: Any = None
    prefetch: Any = None

    def recorded_rounds(self) -> np.ndarray:
        """Round numbers still present in the ring, oldest→newest."""
        start = max(0, self.rounds - self.ring_size)
        return np.arange(start, self.rounds)

    def history(self, *, start_round: int = 0) -> list:
        """Per-round history rows (see the module-level ``history``)."""
        return history(self, start_round=start_round)


def _zero_buffers(step, state0, x0, *, eval_fn, params, ring_alloc, n_evals):
    """Zero-initialized metrics ring + eval buffer with the dtypes the
    round step / eval_fn will write — via ``jax.eval_shape`` over an
    example round input ``x0`` (the store, or a ``CohortBatch`` of
    ``ShapeDtypeStruct``s on the tiered path), so nothing is executed.
    Shared by the single-shot scan, the segment runner, and the tiered
    stream (the buffers must be identical for chunked ≡ single-shot ≡
    tiered bit-equality)."""
    m_shapes = jax.eval_shape(lambda s, x: step(s, x)[1], state0, x0)
    ring0 = {k: jnp.zeros((ring_alloc,), v.dtype)
             for k, v in m_shapes.items()}
    if eval_fn is not None and n_evals:
        e_shapes = jax.eval_shape(eval_fn, params)
        ebuf0 = {k: jnp.zeros((n_evals,), v.dtype)
                 for k, v in e_shapes.items()}
    else:
        ebuf0 = {}
    return ring0, ebuf0


def _scan_rounds(step, state0, ring, ebuf, ts, xs=None, *, ring_alloc,
                 eval_fn=None, eval_every: int = 0,
                 tap: Optional[RoundTap] = None):
    """The engine's inner per-round loop, shared by the store-resident
    ``experiment_core`` (``xs=None`` — the step closes over the store) and
    the tiered ``stream_core`` (``xs`` = the staged cohort stream, leaves
    [len(ts), ...]): scan ``step`` over the global round indices ``ts``,
    ring-buffer each round's metrics (slot = t % ring_alloc), fire the tap
    and the in-scan eval behind their ``lax.cond``s. One loop body means
    the two tiers cannot drift in ring/tap/eval semantics."""
    do_eval = eval_fn is not None and eval_every > 0

    def body(carry, inp):
        state, ring, ebuf = carry
        t, x = inp
        state, metrics = step(state, x)
        slot = jnp.mod(t, ring_alloc)
        ring = {k: ring[k].at[slot].set(metrics[k].astype(ring[k].dtype))
                for k in ring}
        if tap is not None:
            # unordered: ordered io_callbacks are unsupported under cond,
            # and every row carries its round index anyway (obs/taps.py)
            def _emit(args):
                io_callback(tap.emit, None, args[0], args[1], ordered=False)
                return jnp.int32(0)

            jax.lax.cond(jnp.mod(t, tap.every) == 0, _emit,
                         lambda args: jnp.int32(0), (t, metrics))
        if do_eval:
            def run_eval(args):
                buf, p = args
                with scope("fedzo.eval"):
                    vals = eval_fn(p)
                    return {k: buf[k].at[t // eval_every].set(
                        vals[k].astype(buf[k].dtype)) for k in buf}

            ebuf = jax.lax.cond(jnp.mod(t, eval_every) == 0, run_eval,
                                lambda args: args[0], (ebuf, state[0]))
        return (state, ring, ebuf), None

    (state, ring, ebuf), _ = jax.lax.scan(body, (state0, ring, ebuf),
                                          (ts, xs))
    return state, ring, ebuf


def experiment_core(loss_fn, params, store: ClientStore, cfg: FedZOConfig,
                    rounds: int, key, momentum=None, *,
                    algo: Optional[str] = None, strategy=None, zstate=None,
                    eval_fn=None, eval_every: int = 0, ring_size: int = 0,
                    round_fn=None, faults: Optional[FaultModel] = None,
                    fault_state=None, channel_state=None, t0=0,
                    total_rounds: int = 0,
                    ring=None, ebuf=None, tap: Optional[RoundTap] = None):
    """The traceable experiment body: scan ``rounds`` round steps, ring-
    buffer the metrics, eval in-scan every ``eval_every`` rounds. Returns
    (params, momentum, key, fault_state, channel_state, zstate,
    metrics_ring, evals). Un-jitted so sweeps can vmap it over a stacked
    config axis (sim/sweep.py). ``channel_state`` is the wireless-scenario
    carry — required (``ChannelModel.init_state``) when
    ``cfg.channel_model`` is set.

    Segment mode (the checkpointed runner): ``t0``/``total_rounds`` place
    this scan as rounds [t0, t0+rounds) of a ``total_rounds``-round
    experiment — the ring/eval buffers are sized (and slotted) against the
    TOTAL, and partially-filled buffers are threaded back in via
    ``ring``/``ebuf``, so k-round segments write exactly the cells the
    uninterrupted scan would.

    ``tap`` (an ``obs.RoundTap``) streams the metrics of rounds where
    ``t % tap.every == 0`` to the tap's sink live, via an unordered
    ``io_callback`` behind a ``lax.cond``; ``tap=None`` (default) adds
    NOTHING to the trace, preserving the one-host-sync bit-exact program."""
    strat = _resolve(strategy, algo, cfg)
    total = total_rounds or rounds
    ring_alloc = min(total, ring_size) if ring_size else total
    step = make_round_step(loss_fn, cfg, strategy=strat, round_fn=round_fn,
                           faults=faults)
    do_eval = eval_fn is not None and eval_every > 0
    n_evals = (total + eval_every - 1) // eval_every if do_eval else 0

    state0 = (params, momentum, key, fault_state, channel_state, zstate)
    if ring is None or (do_eval and ebuf is None):
        ring0, ebuf0 = _zero_buffers(
            step, state0, store, eval_fn=eval_fn, params=params,
            ring_alloc=ring_alloc, n_evals=n_evals)
        ring = ring0 if ring is None else ring
        ebuf = ebuf0 if ebuf is None else ebuf
    elif ebuf is None:
        ebuf = {}

    ts = jnp.arange(rounds)
    if not (isinstance(t0, int) and t0 == 0):
        ts = ts + t0
    state, ring, ebuf = _scan_rounds(
        lambda s, _: step(s, store), state0, ring, ebuf, ts,
        ring_alloc=ring_alloc, eval_fn=eval_fn, eval_every=eval_every,
        tap=tap)
    params, momentum, key, fault_state, channel_state, zstate = state
    return (params, momentum, key, fault_state, channel_state, zstate,
            ring, ebuf)


def stream_core(loss_fn, params, cfg: FedZOConfig, key, momentum, *,
                strategy=None, zstate=None, xs: CohortBatch, t0,
                total_rounds: int, ring, ebuf, eval_fn=None,
                eval_every: int = 0, ring_size: int = 0, round_fn=None,
                faults: Optional[FaultModel] = None,
                tap: Optional[RoundTap] = None):
    """The traceable tiered-segment body (DESIGN.md §15): scan one
    ``make_cohort_round_step`` per staged round over the cohort stream
    ``xs`` (a ``CohortBatch`` whose leaves carry a leading [S] rounds
    axis). The segment covers global rounds [t0, t0+S) of a
    ``total_rounds``-round experiment; ring/eval buffers are sized and
    slotted against the TOTAL and threaded through, exactly like
    ``experiment_core``'s segment mode — the loop body IS
    ``_scan_rounds``, shared with the resident tier.

    Returns (params, momentum, key, zstate, ring, ebuf). The fault [N]
    chain and the stateful strategies' [N] client masters do NOT appear
    here — the stream host-replays the former into ``xs.avail`` and
    slices the latter into the cohort-shaped ``zstate``."""
    strat = _resolve(strategy, None, cfg)
    seg = xs.sizes.shape[0]
    ring_alloc = min(total_rounds, ring_size) if ring_size else total_rounds
    step = make_cohort_round_step(loss_fn, cfg, strategy=strat,
                                  round_fn=round_fn, faults=faults)
    state0 = (params, momentum, key, zstate)
    ts = jnp.arange(seg) + t0
    state, ring, ebuf = _scan_rounds(
        step, state0, ring, ebuf, ts, xs, ring_alloc=ring_alloc,
        eval_fn=eval_fn, eval_every=eval_every, tap=tap)
    params, momentum, key, zstate = state
    return params, momentum, key, zstate, ring, ebuf


def make_experiment_fn(loss_fn, cfg: FedZOConfig, rounds: int, *,
                       algo: Optional[str] = None, strategy=None,
                       eval_fn=None, eval_every: int = 0,
                       ring_size: int = 0, round_fn=None, faults=None,
                       donate: bool = True, tap=None) -> Callable:
    """Compile the whole experiment once: returns a jitted
    ``fn(params, momentum, key, fstate, cstate, zstate, store) ->
    (params', momentum', key', fstate', cstate', zstate', metrics_ring,
    evals)`` with the carry donated (pass ``momentum=None`` when
    cfg.server_momentum is 0, ``fstate=None`` without a fault model,
    ``cstate=None`` without ``cfg.channel_model``, and ``zstate=None`` for
    the stateless strategies). ``tap`` attaches an in-scan
    ``obs.RoundTap``."""
    strat = _resolve(strategy, algo, cfg)

    def fn(params, momentum, key, fstate, cstate, zstate, store):
        return experiment_core(loss_fn, params, store, cfg, rounds, key,
                               momentum, strategy=strat, zstate=zstate,
                               eval_fn=eval_fn, eval_every=eval_every,
                               ring_size=ring_size, round_fn=round_fn,
                               faults=faults, fault_state=fstate,
                               channel_state=cstate, tap=tap)

    return jax.jit(fn, donate_argnums=(0, 1, 2, 3, 4, 5) if donate else ())


def run_experiment(loss_fn, params, store: ClientStore, cfg: FedZOConfig,
                   rounds: int, *, algo: Optional[str] = None, strategy=None,
                   eval_fn=None, eval_every: int = 0, ring_size: int = 0,
                   key=None, momentum=None, round_fn=None, faults=None,
                   donate: bool = True, checkpoint_every: int = 0,
                   checkpoint_dir=None, resume: bool = False,
                   max_segments=None, segment_callback=None,
                   max_retries: int = 3, lr_backoff: float = 0.5,
                   sink=None, tap_every: Optional[int] = None,
                   tracer=None, stream_segment: int = 8,
                   prefetch: bool = True) -> ExperimentResult:
    """Run a whole experiment inside ONE compiled program.

    The algorithm comes from the strategy registry: ``strategy=`` (a name
    or ``AlgoStrategy`` instance) wins, the ``algo=`` string is a
    deprecated alias, and the default is ``cfg.strategy`` — so swapping
    the algorithm is one config field. Stateful strategies (feddyn,
    scaffold) get their per-client state initialized here and returned as
    ``result.strategy_state``.

    ``eval_fn(params) -> dict of scalars`` must be jit-traceable; it runs
    in-scan every ``eval_every`` rounds. ``ring_size`` bounds the metrics
    buffer (0 keeps every round). With ``donate`` the caller's params /
    momentum / key buffers are consumed — reuse the returned ones.
    ``faults`` attaches a ``sim.faults.FaultModel`` (DESIGN.md §12).

    ``checkpoint_every=k`` (with ``checkpoint_dir``) switches to the
    durable segment runner: the same scan in k-round chunks, one host sync
    + one atomic full-carry snapshot per chunk, bit-identical to the
    single-shot run. ``resume=True`` continues from the latest snapshot in
    ``checkpoint_dir`` (fresh start when there is none). A segment whose
    carry comes back non-finite rolls back to the last good snapshot with
    the lr scaled by ``lr_backoff``, at most ``max_retries`` times, then
    raises ``DivergenceError``. ``max_segments`` bounds the segments run
    this call (for tests/preemption drills); ``segment_callback(round,
    total)`` fires after every successful snapshot.

    Observability: ``sink=`` (an ``obs.MetricsSink``) + ``tap_every=k``
    stream every k-th round's metrics LIVE from inside the scan; both
    default off, which keeps the compiled program byte-identical to the
    pre-obs engine. ``tracer=`` (an ``obs.Tracer``) records compile vs
    execute/segment spans (AOT-compiled, so compile is reported exactly
    once per static shape) and optionally a jax.profiler trace. Every
    result carries ``result.ledger``; runs with a ``checkpoint_dir`` or a
    file-backed sink also write a run manifest next to their artifacts.

    A ``sim.tiered.HostStore`` is dispatched to the tiered cohort-stream
    runner (``tiered.run_tiered_experiment``) — same signature, bitwise
    the same trajectory, host-resident population. ``stream_segment`` /
    ``prefetch`` tune that tier's staging pipeline only; the resident
    scan has no staging and ignores them.
    """
    if not isinstance(store, ClientStore):
        from repro.sim import tiered
        if isinstance(store, tiered.HostStore):
            return tiered.run_tiered_experiment(
                loss_fn, params, store, cfg, rounds, algo=algo,
                strategy=strategy, eval_fn=eval_fn, eval_every=eval_every,
                ring_size=ring_size, key=key, momentum=momentum,
                round_fn=round_fn, faults=faults, donate=donate,
                checkpoint_every=checkpoint_every,
                checkpoint_dir=checkpoint_dir, resume=resume,
                max_segments=max_segments,
                segment_callback=segment_callback,
                max_retries=max_retries, lr_backoff=lr_backoff, sink=sink,
                tap_every=tap_every, tracer=tracer,
                stream_segment=stream_segment, prefetch=prefetch)
        raise TypeError(f"store must be a ClientStore or HostStore, got "
                        f"{type(store).__name__}")
    strat = _resolve(strategy, algo, cfg)
    if key is None:
        key = experiment_key(cfg)
    if momentum is None and strat.has_momentum(cfg):
        momentum = tree_zeros_like(params)
    fstate = faults.init_state(store.n_clients) if faults is not None else None
    channel = cfg.channel_model
    # the chain's round-0 key is folded OFF the experiment key (never a
    # split of the round chain), so channel-off runs keep their key usage
    cstate = (channel.init_state(store.n_clients, channel_lib.init_key(key))
              if channel is not None else None)
    zstate = strat.init_state(params, cfg, store.n_clients)
    do_eval = eval_fn is not None and eval_every > 0
    tap = None
    if tap_every is not None:
        if sink is None:
            raise ValueError("tap_every=k needs a sink= to stream into")
        tap = RoundTap(sink, tap_every)
    # the byte model reads params metadata, so build it BEFORE the run
    # donates the buffers
    ledger = CommsLedger.from_run(cfg, params, channel=channel)
    flat = flat_layout(params, cfg)
    n_clients = store.n_clients
    if checkpoint_every > 0:
        return _run_checkpointed(
            loss_fn, params, store, cfg, rounds, strategy=strat,
            eval_fn=eval_fn, eval_every=eval_every, ring_size=ring_size,
            key=key, momentum=momentum, round_fn=round_fn, faults=faults,
            fstate=fstate, cstate=cstate, zstate=zstate, donate=donate,
            checkpoint_every=checkpoint_every,
            checkpoint_dir=checkpoint_dir, resume=resume,
            max_segments=max_segments, segment_callback=segment_callback,
            max_retries=max_retries, lr_backoff=lr_backoff, tap=tap,
            tracer=tracer, ledger=ledger, flat=flat)
    fn = make_experiment_fn(loss_fn, cfg, rounds, strategy=strat,
                            eval_fn=eval_fn, eval_every=eval_every,
                            ring_size=ring_size, round_fn=round_fn,
                            faults=faults, donate=donate, tap=tap)
    args = (params, momentum, key, fstate, cstate, zstate, store)
    if tracer is not None:
        from repro.checkpoint.checkpoint import config_hash
        ckey = ("experiment", rounds, config_hash(cfg), strat.name,
                eval_every, ring_size, donate, tap is not None)
        with tracer.profile():
            compiled = tracer.timed_compile(ckey, fn, *args)
            with tracer.span("execute", rounds=rounds):
                out = jax.block_until_ready(compiled(*args))
    else:
        out = fn(*args)
    params, momentum, key, fstate, cstate, zstate, ring, ebuf = out
    eval_rounds = np.arange(0, rounds, eval_every) if do_eval \
        else np.arange(0)
    result = ExperimentResult(params=params, momentum=momentum, key=key,
                              metrics=ring, evals=ebuf, rounds=rounds,
                              ring_size=min(rounds, ring_size) or rounds,
                              eval_rounds=eval_rounds, fault_state=fstate,
                              channel_state=cstate,
                              strategy=strat.name, strategy_state=zstate,
                              ledger=ledger)
    sink_path = getattr(sink, "path", None)
    if sink_path:
        result.manifest = obs_manifest.build_manifest(
            cfg, strategy=strat.name, rounds=rounds, n_clients=n_clients,
            ledger=ledger, faults=faults, channel=channel,
            events=result.events, flat=flat,
            extra={"tap_every": tap.every} if tap is not None else None)
        obs_manifest.write_manifest(f"{sink_path}.manifest.json",
                                    result.manifest)
    return result


def _carry_to_state(params, momentum, key, fstate, cstate, zstate, ring,
                    ebuf) -> dict:
    """The durable form of the full experiment carry: one pytree whose
    leaves are all plain arrays (the typed PRNG key is exported via
    ``jax.random.key_data``; ``wrap_key_data`` re-types it on restore).
    A ``None`` zstate (or cstate) contributes no leaves, so snapshots of
    runs without the optional processes keep the historical npz layout —
    and channel-on snapshots carry the fading chain + batteries, so a
    kill-and-resume continues the wireless scenario bit-exactly."""
    return {"params": params, "momentum": momentum,
            "key": jax.random.key_data(key), "fstate": fstate,
            "cstate": cstate, "zstate": zstate, "ring": ring, "ebuf": ebuf}


def _state_to_carry(state: dict, cfg: FedZOConfig):
    """Inverse of ``_carry_to_state``. Host numpy leaves are put back on
    device here so the segment fn's donation always sees jax arrays."""
    key = jax.random.wrap_key_data(jnp.asarray(state["key"]),
                                   impl=cfg.prng_impl)
    dev = [jax.tree.map(jnp.asarray, state[k])
           for k in ("params", "momentum", "fstate", "cstate", "zstate",
                     "ring", "ebuf")]
    return (dev[0], dev[1], key, dev[2], dev[3], dev[4], dev[5], dev[6])


def _finite_state(state: dict, rounds_done, ring_alloc, eval_every,
                  do_eval) -> bool:
    """Host-side divergence check on a fetched carry: every param leaf and
    every metric/eval cell written by the rounds in ``rounds_done`` must be
    finite. Boolean masks and counters pass through ``isfinite`` trivially,
    so the check is a plain sweep over the written cells."""
    for leaf in jax.tree.leaves(state["params"]):
        if not np.all(np.isfinite(leaf)):
            return False
    slots = np.unique([t % ring_alloc for t in rounds_done])
    for v in state["ring"].values():
        if np.issubdtype(v.dtype, np.floating) and \
                not np.all(np.isfinite(v[slots])):
            return False
    if do_eval:
        eslots = np.unique([t // eval_every for t in rounds_done
                            if t % eval_every == 0])
        for v in state["ebuf"].values():
            if eslots.size and np.issubdtype(v.dtype, np.floating) and \
                    not np.all(np.isfinite(v[eslots])):
                return False
    return True


def _run_checkpointed(loss_fn, params, store, cfg, rounds, *, strategy,
                      eval_fn, eval_every, ring_size, key, momentum,
                      round_fn, faults, fstate, cstate, zstate, donate,
                      checkpoint_every, checkpoint_dir, resume,
                      max_segments, segment_callback, max_retries,
                      lr_backoff, tap=None, tracer=None,
                      ledger=None, flat=None) -> ExperimentResult:
    """The durable segment loop behind ``run_experiment(...,
    checkpoint_every=k)``. Invariants:

    - **Bit-equality**: segments scan global round indices into buffers
      sized against the total, so the chunked run writes exactly the cells
      (and walks exactly the key chain) of the single-shot scan. The
      strategy carry rides the same snapshot, so a resumed scaffold/feddyn
      run restores every client's control/dual bit-identically.
    - **Durability**: the full carry is snapshotted atomically after every
      segment (``checkpoint.save_run_state``: tmp dir + rename + LATEST
      pointer swap), so a SIGKILL at ANY point leaves a consistent latest
      snapshot; ``resume=True`` continues from it.
    - **Recovery**: a non-finite post-segment carry rolls the run back to
      the last good snapshot, scales lr by ``lr_backoff``, and retries —
      at most ``max_retries`` times, then ``DivergenceError``. Every
      rollback appends a structured ``{"round", "event": "rollback", ...}``
      row to ``result.events`` (and the snapshot meta, so a resumed run
      keeps the full recovery log).
    """
    from repro.checkpoint import checkpoint as ckpt

    strat = _resolve(strategy, None, cfg)
    if checkpoint_dir is None:
        raise ValueError("checkpoint_every > 0 requires checkpoint_dir")
    do_eval = eval_fn is not None and eval_every > 0
    ring_alloc = min(rounds, ring_size) if ring_size else rounds
    n_evals = (rounds + eval_every - 1) // eval_every if do_eval else 0
    orig_hash = ckpt.config_hash(cfg)

    ring, ebuf = _zero_buffers(
        make_round_step(loss_fn, cfg, strategy=strat, round_fn=round_fn,
                        faults=faults),
        (params, momentum, key, fstate, cstate, zstate), store,
        eval_fn=eval_fn, params=params, ring_alloc=ring_alloc,
        n_evals=n_evals)

    t, events, cur_lr = 0, [], cfg.lr
    if resume:
        snap = ckpt.latest_run_state(checkpoint_dir)
        if snap is not None:
            like = _carry_to_state(params, momentum, key, fstate, cstate,
                                   zstate, ring, ebuf)
            state, meta = ckpt.restore_run_state(snap, like)
            if meta.get("config_hash") not in (None, orig_hash):
                import warnings
                warnings.warn(
                    f"resuming from a snapshot of a DIFFERENT config "
                    f"(hash {meta.get('config_hash')} != {orig_hash}) — "
                    f"the continued trajectory will not match either run")
            t = int(meta["round"])
            events = list(meta.get("events", []))
            cur_lr = float(meta.get("lr", cfg.lr))
            params, momentum, key, fstate, cstate, zstate, ring, ebuf = \
                _state_to_carry(state, cfg)

    def checkpoint_meta():
        return {"round": t, "rounds_total": rounds, "algo": strat.name,
                "strategy": strat.name, "config_hash": orig_hash,
                "lr": cur_lr, "events": events}

    def write_run_manifest():
        man = obs_manifest.build_manifest(
            cfg, strategy=strat.name, rounds=rounds,
            n_clients=store.n_clients, ledger=ledger, faults=faults,
            channel=cfg.channel_model, events=events, flat=flat,
            extra={"checkpoint_every": checkpoint_every, "lr": cur_lr,
                   "rounds_done": t,
                   "tap_every": tap.every if tap is not None else None})
        obs_manifest.write_manifest(checkpoint_dir, man)
        return man

    if t == 0:
        # round-0 snapshot: the rollback anchor for a first-segment
        # divergence (the donated pre-segment carry is gone by then)
        state0 = jax.device_get(
            _carry_to_state(params, momentum, key, fstate, cstate, zstate,
                            ring, ebuf))
        ckpt.save_run_state(checkpoint_dir, state0, round_idx=0,
                            meta=checkpoint_meta())
    write_run_manifest()   # provisional: rewritten with final events below

    seg_fns: dict = {}

    def segment_fn(chunk):
        if chunk not in seg_fns:
            run_cfg = (cfg if cur_lr == cfg.lr
                       else dataclasses.replace(cfg, lr=cur_lr))

            def fn(params, momentum, key, fstate, cstate, zstate, ring,
                   ebuf, t0, store):
                return experiment_core(
                    loss_fn, params, store, run_cfg, chunk, key, momentum,
                    strategy=strat, zstate=zstate, eval_fn=eval_fn,
                    eval_every=eval_every, ring_size=ring_size,
                    round_fn=round_fn, faults=faults, fault_state=fstate,
                    channel_state=cstate, t0=t0, total_rounds=rounds,
                    ring=ring, ebuf=ebuf, tap=tap)

            seg_fns[chunk] = jax.jit(
                fn,
                donate_argnums=(0, 1, 2, 3, 4, 5, 6, 7) if donate else ())
        return seg_fns[chunk]

    def span(name, **meta):
        return (tracer.span(name, **meta) if tracer is not None
                else nullcontext())

    retries, segments_done = 0, 0
    with (tracer.profile() if tracer is not None else nullcontext()):
        while t < rounds:
            chunk = min(checkpoint_every, rounds - t)
            jitted = segment_fn(chunk)
            args = (params, momentum, key, fstate, cstate, zstate, ring,
                    ebuf, jnp.int32(t), store)
            if tracer is not None:
                # one compile span per (chunk size, lr) program — reused
                # executable across same-shape segments
                run = tracer.timed_compile(
                    ("segment", chunk, cur_lr, orig_hash), jitted, *args)
            else:
                run = jitted
            # the segment's host phases, each named on the profiler clock
            # by the segment's first round (the index its ring rows carry)
            t0 = t
            with span("segment", t0=t0, chunk=chunk):
                with span("segment.dispatch", t0=t0):
                    out = run(*args)
                # ONE host sync per segment: fetch the full carry, then
                # everything below (divergence check + atomic save) is
                # host-side numpy
                with span("segment.fetch", t0=t0):
                    state = jax.device_get(_carry_to_state(*out))
                t_next = t + chunk
                if not _finite_state(state, range(t, t_next), ring_alloc,
                                     eval_every, do_eval):
                    retries += 1
                    if retries > max_retries:
                        raise DivergenceError(t_next, max_retries, cur_lr)
                    cur_lr *= lr_backoff
                    events.append({"round": t_next, "event": "rollback",
                                   "from_round": t, "retry": retries,
                                   "lr": cur_lr})
                    seg_fns.clear()  # the backed-off lr is baked into the
                    if tracer is not None:   # program (and its executable)
                        tracer.invalidate_compiled()
                    snap = ckpt.latest_run_state(checkpoint_dir)
                    good, _ = ckpt.restore_run_state(snap, state)
                    params, momentum, key, fstate, cstate, zstate, ring, \
                        ebuf = _state_to_carry(good, cfg)
                    continue
                retries = 0
                params, momentum, key, fstate, cstate, zstate, ring, ebuf = \
                    out
                t = t_next
                with span("checkpoint.save", t0=t0):
                    ckpt.save_run_state(checkpoint_dir, state, round_idx=t,
                                        meta=checkpoint_meta())
            segments_done += 1
            if segment_callback is not None:
                segment_callback(t, rounds)
            if max_segments is not None and segments_done >= max_segments:
                break

    manifest = write_run_manifest()   # final: full event stream, rounds_done
    eval_rounds = np.arange(0, t, eval_every) if do_eval else np.arange(0)
    return ExperimentResult(params=params, momentum=momentum, key=key,
                            metrics=ring, evals=ebuf, rounds=t,
                            ring_size=ring_alloc, eval_rounds=eval_rounds,
                            fault_state=fstate, channel_state=cstate,
                            events=list(events),
                            strategy=strat.name, strategy_state=zstate,
                            ledger=ledger, manifest=manifest)


def history(result: ExperimentResult, *, start_round: int = 0) -> list:
    """FedServer-style per-round history from an engine result: ONE host
    sync for everything (metrics ring + evals), then plain python floats.
    Every row carries the run's ``strategy`` name so multi-algorithm
    sweeps/comparisons stay distinguishable once rows are pooled.

    Eval rounds evicted from the metrics ring (a long run with a small
    ``ring_size``) still surface as eval-only rows — the in-scan evals live
    in their own [n_evals] buffer, so the full accuracy curve survives
    however small the ring is.

    Results carrying a comms ledger (every ``run_experiment`` result) get
    the byte columns appended host-side: per-round ``wire_bytes`` /
    ``dense_bytes`` / ``downlink_bytes``, cumulative ``wire_bytes_total``
    / ``downlink_bytes_total``, ``compression_ratio``, and
    ``wire_bytes_effective`` on rows that report ``m_effective``. They are
    annotations, NOT ring contents — the in-scan metric set (and thus the
    compiled program and the golden fixtures) is untouched. Tiered runs
    additionally carry ``result.staging`` (round -> bucket id / staged
    bytes), merged into the same rows by the ledger."""
    mets = jax.device_get(result.metrics)
    evals = jax.device_get(result.evals)
    ev_by_round = {int(t): {k: float(v[i]) for k, v in evals.items()}
                   for i, t in enumerate(result.eval_rounds)}
    ring_start = max(0, result.rounds - result.ring_size)
    out = []
    for t in sorted(ev_by_round):
        if t < ring_start:                  # evicted from the ring: eval-only
            out.append({"round": start_round + t,
                        "strategy": result.strategy, **ev_by_round[t]})
    for t in result.recorded_rounds():
        row = {"round": start_round + int(t), "strategy": result.strategy}
        slot = int(t) % result.ring_size
        row.update({k: float(v[slot]) for k, v in mets.items()})
        row.update(ev_by_round.get(int(t), {}))
        out.append(row)
    # structured host-side events (divergence rollbacks) interleave by
    # round — a rollback at round t sorts before round t's successful retry
    if result.events:
        out.extend({**e, "round": start_round + int(e["round"])}
                   for e in result.events)
        out.sort(key=lambda r: (r["round"], "event" not in r))
    if result.ledger is not None:
        result.ledger.annotate(out, staging=result.staging,
                               start_round=start_round)
    return out
