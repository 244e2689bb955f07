"""Chip smoke test: the FedZO engine and its Pallas kernels, compiled on a TPU.

    python chip_smoke.py             # one chip: phases (a), (b) and (c)
    python chip_smoke.py --chips 4   # the clients-mesh round on four chips,
                                     # against the unsharded round

(a) The flat-buffer kernels at real size (a 2^26-element fp32 buffer; the
    AirComp reduce at M=10 over 2^24 and M=50 over 2^22), each against its
    oracle in ``kernels/ref.py`` computed on the same chip.
(b) Algorithm 1 on the quickstart federation (N=50, M=10, H=5, b1=25, b2=20,
    softmax regression, d=7,850) for 10 rounds on the flat-kernel path,
    against the pure-XLA path with the same direction convention.
(c) Algorithm 1 with AirComp (paper Sec. IV) on the CNN track for 10 rounds,
    against the pytree path of the same config.

All data and weights are generated from ``--seed``. The script needs a TPU:
when JAX finds none, or when any phase fails, it exits non-zero and prints
no result. Its last stdout line is one JSON object naming the device. The
times it prints are informational.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

# Phase (a) tolerances. Elementwise outputs are O(1-10); on a v5e the
# kernels' walk, replay and mean match the XLA oracles bit for bit. The
# squared norms sum 2^22-2^26 terms as sequential per-block partials whose
# in-block reduction order differs: 1.2e-7 relative on a v5e.
KERNEL_ATOL = 1e-5
NORM_RTOL = 1e-5
# Phase (b) and the four-chip mode: per-round mean local loss, relative to
# the reference. The two runs order their fp32 ops differently (walked vs
# fresh perturbations; sharded vs whole-cohort sums), and the estimator
# amplifies that: one ulp of loss is ~1% of a coefficient c = d·ΔL/μ at
# d=7,850, μ=1e-3. So the gap is held tight on round 0, where it is
# round-off (1e-5 on a v5e and on the CPU; a different direction stream
# moves it by ~1e-2), and looser over all rounds, where it compounds
# (after 10 rounds: 3.9e-4 flat vs reference on one v5e, 3.4e-4 sharded vs
# unsharded on four).
LOSS_RTOL_ROUND0 = 1e-4
LOSS_RTOL = 1e-3
# Phase (c): round-0 Δ_max and Eq.-17 noise std, relative (at most 1.7e-4
# on a v5e, 6e-4 on the CPU; other directions move them by tens of percent).
AIR_RTOL = 2e-3


def log(msg):
    print(msg, flush=True)


def _has_kernel(text: str) -> bool:
    return "tpu_custom_call" in text


def _require_kernel(what: str, text: str):
    if not _has_kernel(text):
        raise AssertionError(f"{what}: no tpu_custom_call in the compiled "
                             f"program — the Pallas kernels were not "
                             f"compiled")


# -- (a) kernels at real size ------------------------------------------------


def _kernel_case(name, fn, ref_fn, args, checks):
    """Compile ``fn`` (must hold a Mosaic kernel), run it and its oracle on
    the chip, and hold each output to its check: ("abs", atol) on the max
    abs error or ("rel", rtol) on max abs error over max |oracle|."""
    import jax
    import jax.numpy as jnp

    t0 = time.perf_counter()
    compiled = jax.jit(fn).lower(*args).compile()
    t_compile = time.perf_counter() - t0
    _require_kernel(name, compiled.as_text())
    t0 = time.perf_counter()
    outs = jax.block_until_ready(compiled(*args))
    t_run = time.perf_counter() - t0
    wants = jax.jit(ref_fn)(*args)

    @jax.jit
    def errs(o, w):
        a = jnp.max(jnp.abs(o - w))
        return a, a / jnp.maximum(jnp.max(jnp.abs(w)), 1e-30)

    for label, o, w, (kind, tol) in zip(name.split("|"), outs, wants, checks):
        abs_err, rel_err = (float(e) for e in errs(o, w))
        bound = abs_err if kind == "abs" else rel_err
        log(f"[a] {label}: shape={tuple(o.shape)} compile_s={t_compile:.2f} "
            f"run_s={t_run:.3f} max_abs_err={abs_err:.3e} "
            f"max_rel_err={rel_err:.3e} ({kind} tol {tol:g})")
        if not bound <= tol:
            raise AssertionError(f"{label}: {kind} error {bound:.3e} > {tol}")


def phase_kernels(seed, d=2 ** 26, b2=20,
                  aircomp=((10, 2 ** 24), (50, 2 ** 22))):
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops, ref

    key = jax.random.key(seed)
    kx, kc, kk, kd = jax.random.split(key, 4)
    key2 = ops.key_words(kk)
    x = jax.random.normal(kx, (d,), jnp.float32)
    nn = jnp.asarray([3, 4], jnp.int32)
    ab = jnp.asarray([-0.5, 0.75], jnp.float32)
    coeffs = 0.1 * jax.random.normal(kc, (b2,), jnp.float32)
    d_valid = d - 1000                                  # masks a pad tail

    _kernel_case(
        "zo_walk",
        lambda x, k: (ops.zo_walk(x, k, nn, ab),),
        lambda x, k: (ref.zo_walk_ref(x.reshape(-1, 128), k, nn,
                                      ab).reshape(-1),),
        (x, key2), [("abs", KERNEL_ATOL)])
    _kernel_case(
        "zo_replay",
        lambda x, k, c: (ops.zo_replay(x, k, c),),
        lambda x, k, c: (ref.zo_replay_ref(x.reshape(-1, 128), k,
                                           c).reshape(-1),),
        (x, key2, coeffs), [("abs", KERNEL_ATOL)])
    del x
    _kernel_case(
        "zo_dirnorms",
        lambda k: (ops.zo_dirnorms(k, d_valid, b2=b2, n_pad=d),),
        lambda k: (ref.zo_dirnorms_ref(k, d_valid, b2, d),),
        (key2,), [("rel", NORM_RTOL)])

    for m, n in aircomp:
        km, ks = jax.random.split(jax.random.fold_in(kd, m))
        deltas = jax.random.normal(km, (m, n), jnp.float32)
        mask = jax.random.bernoulli(ks, 0.7, (m,)).astype(jnp.float32)
        scale = mask / jnp.maximum(jnp.sum(mask), 1.0)
        n_valid = n - 37

        def ref_fn(x, s, m=m, n_valid=n_valid):
            mean, sq = ref.aircomp_reduce_ref(x.reshape(m, -1, 128), s,
                                              n_valid)
            return mean.reshape(-1), sq

        _kernel_case(
            f"aircomp_reduce_m{m}_mean|aircomp_reduce_m{m}_sq",
            lambda x, s, n_valid=n_valid: ops.aircomp_reduce(x, s, n_valid),
            ref_fn, (deltas, scale),
            [("abs", KERNEL_ATOL), ("rel", NORM_RTOL)])
        del deltas


# -- (b) Algorithm 1 on the flat-kernel path ---------------------------------


def quickstart_task(seed):
    """The quickstart federation: 6,000 training rows of a 784-feature,
    10-class problem dealt as label-sorted shards to N=50 clients."""
    from repro.workloads import neural
    return neural.make_task("softmax", n_train=6000, n_test=1000,
                            n_clients=50, partition="shards", seed=seed)


def quickstart_config(task, seed, **overrides):
    from repro.workloads import neural
    kw = dict(n_participating=10, lr=1e-3, weight_by_size=False, seed=seed,
              flat_params=True)
    kw.update(overrides)
    return neural.default_config(task, **kw)


def _run(task, cfg, rounds, *, what, expect_kernel, **kw):
    """One experiment through ``neural.run`` (→ ``sim.run_experiment``),
    AOT-compiled under a tracer so compile and execute time separate."""
    from repro.obs import Tracer
    from repro.workloads import neural

    tr = Tracer()
    res = neural.run(task, cfg, rounds, tracer=tr, **kw)
    texts = [c.as_text() for c in tr.executables()]
    if expect_kernel:
        _require_kernel(what, "\n".join(texts))
    log(f"    {what}: compile_s={tr.total('compile'):.2f} "
        f"execute_s={tr.total('execute'):.3f} "
        f"tpu_custom_call={any(_has_kernel(t) for t in texts)}")
    return res


def _losses(res):
    import numpy as np
    return np.asarray(res.metrics["mean_local_loss"], np.float64)


def _compare_losses(tag, got, want):
    import numpy as np
    rel = np.abs(got - want) / np.abs(want)
    log(f"[{tag}] losses: {np.array2string(got, precision=6)}")
    log(f"[{tag}] reference: {np.array2string(want, precision=6)}")
    log(f"[{tag}] rel_loss_diff round 0={rel[0]:.3e} (tol "
        f"{LOSS_RTOL_ROUND0:g}), max={rel.max():.3e} (tol {LOSS_RTOL:g})")
    if not np.all(np.isfinite(got)):
        raise AssertionError(f"{tag}: non-finite loss")
    if not (rel[0] <= LOSS_RTOL_ROUND0 and rel.max() <= LOSS_RTOL):
        raise AssertionError(f"{tag}: loss off the reference "
                             f"(round 0 {rel[0]:.3e}, max {rel.max():.3e})")
    if not got[-1] < got[0]:
        raise AssertionError(f"{tag}: loss did not descend "
                             f"({got[0]} -> {got[-1]})")


def phase_flat_round(seed, rounds=10):
    task = quickstart_task(seed)
    cfg = quickstart_config(task, seed)
    ref_cfg = dataclasses.replace(cfg, flat_params=False,
                                  direction_conv="counter")
    log(f"[b] quickstart flat path: N={cfg.n_devices} "
        f"M={cfg.n_participating} H={cfg.local_iters} b1={cfg.b1} "
        f"b2={cfg.b2} rounds={rounds}")
    res = _run(task, cfg, rounds, what="flat experiment", eval_every=0,
               expect_kernel=True)
    ref = _run(task, ref_cfg, rounds, what="pytree counter reference",
               eval_every=0, expect_kernel=False)
    _compare_losses("b", _losses(res), _losses(ref))


# -- (c) Algorithm 1 with AirComp on the CNN track ---------------------------


def phase_aircomp_cnn(seed, rounds=10, eval_every=3, **task_kw):
    import jax
    import numpy as np

    from repro.workloads import neural

    task = neural.make_task("cnn", seed=seed, **task_kw)
    cfg = neural.default_config(task, flat_params=True, aircomp=True,
                                channel_schedule=True, snr_db=0.0,
                                h_min=0.8, seed=seed)
    ref_cfg = dataclasses.replace(cfg, flat_params=False,
                                  direction_conv="counter")
    untrained = float(jax.jit(task.accuracy)(neural.params_init(task, seed),
                                             task.test))
    log(f"[c] cnn aircomp: clients={cfg.n_devices} M={cfg.n_participating} "
        f"H={cfg.local_iters} b2={cfg.b2} snr_db={cfg.snr_db} "
        f"h_min={cfg.h_min} rounds={rounds}")
    res = _run(task, cfg, rounds, what="flat aircomp experiment",
               eval_every=eval_every, expect_kernel=True)
    ref = _run(task, ref_cfg, rounds, what="pytree counter reference",
               eval_every=eval_every, expect_kernel=False)

    m_eff = np.asarray(res.metrics["m_effective"])
    m_ref = np.asarray(ref.metrics["m_effective"])
    log(f"[c] m_effective={m_eff.tolist()} reference={m_ref.tolist()}")
    if not np.array_equal(m_eff, m_ref):
        raise AssertionError("m_effective differs from the reference")
    for k in ("delta_max", "aircomp_noise_std"):
        got = float(res.metrics[k][0])
        want = float(ref.metrics[k][0])
        rel = abs(got - want) / abs(want)
        log(f"[c] round-0 {k}={got:.6e} reference={want:.6e} "
            f"rel_diff={rel:.3e} (tol {AIR_RTOL:g})")
        if not rel <= AIR_RTOL:
            raise AssertionError(f"round-0 {k} off by {rel:.3e}")
    # at 0 dB the Eq.-17 noise makes the 10-round loss far from monotone,
    # so "descends" means some later round beats round 0
    losses = _losses(res)
    log(f"[c] losses: {np.array2string(losses, precision=6)}")
    if not (np.all(np.isfinite(losses)) and losses[1:].min() < losses[0]):
        raise AssertionError(f"cnn loss not finite and descending: {losses}")
    acc = np.asarray(res.evals["test_acc"])
    log(f"[c] test_acc at rounds {res.eval_rounds.tolist()}: "
        f"{acc.tolist()} (untrained {untrained:.4f})")
    if not acc[-1] > untrained:
        raise AssertionError(f"test accuracy {acc[-1]} not above the "
                             f"untrained model's {untrained}")


# -- four chips: the clients-mesh round ---------------------------------------


def phase_sharded(seed, rounds=10, n_devices=4):
    from repro import sim
    from repro.workloads import neural

    mesh = sim.make_clients_mesh()
    if mesh.shape["clients"] != n_devices:
        raise AssertionError(f"need {n_devices} devices for the clients "
                             f"mesh, found {mesh.shape['clients']}")
    task = quickstart_task(seed)
    # M=10 does not split over four chips; 12 gives each chip 3 clients
    cfg = quickstart_config(task, seed, n_participating=12)
    log(f"[4] clients mesh {dict(mesh.shape)}: M={cfg.n_participating} "
        f"rounds={rounds}")
    res = _run(task, cfg, rounds, what="sharded experiment", eval_every=0,
               mesh=mesh, expect_kernel=True)
    ref = _run(task, cfg, rounds, what="unsharded experiment",
               eval_every=0, expect_kernel=True)
    out_devs = res.params["w"].sharding.device_set
    ref_devs = ref.params["w"].sharding.device_set
    log(f"[4] sharded round output on {len(out_devs)} devices; unsharded "
        f"on {len(ref_devs)} ({sorted(d.id for d in ref_devs)})")
    if len(out_devs) != n_devices:
        raise AssertionError(f"sharded output lives on {len(out_devs)} "
                             f"devices, not {n_devices}")
    _compare_losses("4", _losses(res), _losses(ref))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from repro.utils import compile_cache
    cache_dir = compile_cache.enable()
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform {dev.platform!r})",
              file=sys.stderr)
        return 1
    log(f"device: {dev.platform} {dev.device_kind} count={len(devices)} "
        f"jax={jax.__version__} compile_cache={cache_dir}")
    phases = ([("sharded", phase_sharded)] if args.chips == 4 else
              [("a", phase_kernels), ("b", phase_flat_round),
               ("c", phase_aircomp_cnn)])
    for name, fn in phases:
        t0 = time.perf_counter()
        fn(args.seed)
        log(f"phase {name}: ok wall_s={time.perf_counter() - t0:.1f}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
