import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch × input-shape × mesh) combo
with ShapeDtypeStruct inputs (no allocation) and extract the roofline terms.

    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2-0.5b \
        --shape train_4k [--multi-pod] [--algo fedzo|fedavg] [--out out.json]

The XLA_FLAGS line above MUST run before any jax import (jax locks the
device count on first init); keep it the first statement of this module.
Results (memory analysis, HLO FLOPs/bytes, per-collective byte counts,
derived roofline seconds) are appended as JSON for EXPERIMENTS.md.
"""
import argparse
import json
import re
import sys
import time

import jax

jax.config.update("jax_threefry_partitionable", True)
if os.environ.get("REPRO_RNG", "") == "rbg":
    # single-op RngBitGenerator: collapses the multi-stage threefry pipeline
    # whose per-stage buffers dominate ZO perturbation memory on big models
    jax.config.update("jax_default_prng_impl", "rbg")

import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import get_config, get_shape, ARCH_IDS, SHAPE_IDS
from repro.configs.base import FedZOConfig
from repro.core import fedavg, fedzo
from repro.launch.mesh import make_production_mesh
from repro.launch import sharding as shr
from repro.models.api import build, decode_width
from repro.utils import hw

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_DTYPE_BYTES = {"f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "s8": 1,
                "u8": 1, "pred": 1, "f64": 8, "s64": 8, "u64": 8, "c64": 8,
                "f8e4m3fn": 1, "f8e5m2": 1, "s16": 2, "u16": 2}


def _shape_bytes(type_str):
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES.get(dt, 4)
    return total


def parse_collectives(hlo_text):
    """Trip-count-weighted collective bytes per device, by type.

    Collectives inside scan/while bodies execute once per iteration; XLA
    annotates compiled while ops with ``known_trip_count``, so we build the
    computation call graph (while body= references) and weight each body's
    collective bytes by its trip count, recursively. Unannotated whiles
    count once (conservative lower bound).
    """
    comp_coll = {}     # computation -> {type: bytes}, {type: count}
    comp_calls = {}    # computation -> [(callee, trips)]
    entry = None
    cur = None
    coll_re = re.compile(
        r"%?[\w.\-]+\s*=\s*(.+?)\s+(" + "|".join(COLLECTIVES) +
        r")(-start|-done)?\(")
    head_re = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s+\(.*\)\s+->")
    body_re = re.compile(r"body=%?([\w.\-]+)")
    trips_re = re.compile(r'known_trip_count\D*?(\d+)')
    call_re = re.compile(r"(?:to_apply|branch_computations)=\{?%?([\w.\-,% ]+)")

    for line in hlo_text.splitlines():
        if not line.startswith(" "):
            m = head_re.match(line.strip())
            if m:
                cur = m.group(2)
                comp_coll[cur] = ({c: 0 for c in COLLECTIVES},
                                  {c: 0 for c in COLLECTIVES})
                comp_calls[cur] = []
                if m.group(1):
                    entry = cur
            continue
        if cur is None:
            continue
        ls = line.strip()
        m = coll_re.match(ls)
        if m and m.group(3) != "-done":
            comp_coll[cur][0][m.group(2)] += _shape_bytes(m.group(1))
            comp_coll[cur][1][m.group(2)] += 1
        if " while(" in ls:
            mb = body_re.search(ls)
            if mb:
                mt = trips_re.search(ls)
                trips = int(mt.group(1)) if mt else 1
                comp_calls[cur].append((mb.group(1), trips))
        elif "to_apply=" in ls and "fusion" not in ls.split("=", 1)[1][:60]:
            mc = call_re.search(ls)
            if mc:
                for callee in mc.group(1).replace("%", "").split(","):
                    comp_calls[cur].append((callee.strip(), 1))

    memo = {}

    def total(comp, depth=0):
        if comp in memo or depth > 50 or comp not in comp_coll:
            return memo.get(comp, ({c: 0 for c in COLLECTIVES},
                                   {c: 0 for c in COLLECTIVES}))
        b = dict(comp_coll[comp][0])
        n = dict(comp_coll[comp][1])
        for callee, trips in comp_calls.get(comp, ()):  # noqa: B020
            cb, cn = total(callee, depth + 1)
            for c in COLLECTIVES:
                b[c] += trips * cb[c]
                n[c] += trips * cn[c]
        memo[comp] = (b, n)
        return memo[comp]

    if entry is None and comp_coll:
        entry = next(iter(comp_coll))
    return total(entry) if entry else ({c: 0 for c in COLLECTIVES},
                                       {c: 0 for c in COLLECTIVES})


def count_params(specs, cfg):
    total = sum(int(l.size) for l in jax.tree.leaves(specs))
    if not cfg.n_experts:
        return total, total
    flat, _ = jax.tree_util.tree_flatten_with_path(specs)
    expert = sum(int(l.size) for kp, l in flat
                 if shr._is_expert(jax.tree_util.keystr(kp)))
    active = total - expert + expert * cfg.top_k / cfg.n_experts
    return total, int(active)


def build_case(arch, shape_name, *, multi_pod, algo="fedzo", b2=1, h=2,
               estimator="sphere", direction_dtype="float32", donate=False):
    cfg = get_config(arch)
    shape = get_shape(shape_name)
    model = build(cfg)
    mesh = make_production_mesh(multi_pod=multi_pod)
    fedcfg = FedZOConfig(b2=b2, local_iters=h, estimator=estimator,
                         direction_dtype=direction_dtype)

    pspecs = model.param_specs()
    psh = shr.param_shardings(pspecs, mesh)
    params_in = jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        pspecs, psh)

    bshapes = model.batch_shapes(shape)
    bspecs = {k: jax.ShapeDtypeStruct(s, d) for k, (s, d) in bshapes.items()}
    bsh = shr.batch_shardings(bspecs, mesh)
    batch_in = {k: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=bsh[k])
                for k, v in bspecs.items()}
    rng_in = jax.ShapeDtypeStruct(
        (), jax.eval_shape(lambda: jax.random.key(0)).dtype,
        sharding=NamedSharding(mesh, P()))

    if shape.kind == "train":
        loss = lambda p, b: model.loss(p, b, mesh=mesh)
        if algo == "fedavg":
            raw = fedavg.make_train_step(loss, fedcfg)
        elif multi_pod:
            n_pod = mesh.shape["pod"]
            loss_g = lambda p, b: model.loss(p, b, mesh=mesh, n_groups=n_pod)
            raw = fedzo.make_pod_round_step(loss_g, fedcfg, mesh)
        else:
            raw = fedzo.make_train_step(loss, fedcfg)
        fn = jax.jit(raw, out_shardings=(psh, None),
                     donate_argnums=(0,) if donate else ())
        args = (params_in, batch_in, rng_in)
    elif shape.kind == "prefill":
        width = min(shape.seq_len, 32_768)

        def raw(p, b):
            return model.prefill(p, b, width, mesh=mesh)

        cache_specs = jax.eval_shape(raw, pspecs, bspecs)[1]
        csh = shr.cache_shardings(cache_specs, mesh, cfg)
        fn = jax.jit(raw, out_shardings=(None, csh))
        args = (params_in, batch_in)
    else:  # decode
        width = decode_width(cfg, shape)
        window = cfg.long_context_window if shape.seq_len > 65_536 else 0
        cache_specs = jax.eval_shape(
            lambda: model.init_cache(shape.global_batch, width))
        csh = shr.cache_shardings(cache_specs, mesh, cfg)
        cache_in = jax.tree.map(
            lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
            cache_specs, csh)
        pos_in = jax.ShapeDtypeStruct((), jnp.int32,
                                      sharding=NamedSharding(mesh, P()))

        def raw(p, b, cache, pos):
            return model.decode(p, b, cache, pos, mesh=mesh, window=window)

        fn = jax.jit(raw, out_shardings=(None, csh),
                     donate_argnums=(2,) if donate else ())
        args = (params_in, batch_in, cache_in, pos_in)

    return cfg, shape, mesh, model, pspecs, fn, args


def run_case(arch, shape_name, *, multi_pod, algo="fedzo", b2=1, h=2,
             estimator="sphere", direction_dtype="float32", donate=False):
    t0 = time.time()
    cfg, shape, mesh, model, pspecs, fn, args = build_case(
        arch, shape_name, multi_pod=multi_pod, algo=algo, b2=b2, h=h,
        estimator=estimator, direction_dtype=direction_dtype, donate=donate)
    lowered = fn.lower(*args)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    ma = compiled.memory_analysis()
    mem = {k: int(getattr(ma, k)) for k in
           ("argument_size_in_bytes", "output_size_in_bytes",
            "temp_size_in_bytes", "generated_code_size_in_bytes")}
    mem["total_bytes_per_device"] = (mem["argument_size_in_bytes"] +
                                     mem["temp_size_in_bytes"] +
                                     mem["output_size_in_bytes"])
    ca = dict(compiled.cost_analysis() or {})
    flops = float(ca.get("flops", 0.0))
    bytes_accessed = float(ca.get("bytes accessed", 0.0))
    coll, coll_counts = parse_collectives(compiled.as_text())
    coll_total = float(sum(coll.values()))

    agg = None
    if multi_pod and shape.kind == "train":
        # separately lower the dense-uplink aggregation program (per-pod
        # deltas -> mean) so the full-d cross-pod all-reduce is priced even
        # though the round program itself exchanges only coefficients.
        n_pod = mesh.shape["pod"]
        psh_pod = jax.tree.map(
            lambda s, sh: jax.ShapeDtypeStruct(
                (n_pod,) + s.shape, s.dtype,
                sharding=NamedSharding(mesh, P(*(("pod",) + tuple(sh.spec))))),
            pspecs, shr.param_shardings(pspecs, mesh))
        rng2 = jax.ShapeDtypeStruct(
            (), jax.eval_shape(lambda: jax.random.key(0)).dtype,
            sharding=NamedSharding(mesh, P()))
        agg_fn = jax.jit(fedzo.make_delta_agg_step(
            FedZOConfig(aircomp=True, snr_db=0.0), n_pod))
        agg_c = agg_fn.lower(psh_pod, rng2).compile()
        a_coll, _ = parse_collectives(agg_c.as_text())
        a_ma = agg_c.memory_analysis()
        agg = {"collective_bytes_per_device": a_coll,
               "temp_bytes": int(a_ma.temp_size_in_bytes),
               "collective_total_bytes": float(sum(a_coll.values()))}

    n_chips = 1
    for v in mesh.shape.values():
        n_chips *= v
    roof = hw.roofline_seconds(flops, bytes_accessed, coll_total, chips=1)
    n_params, n_active = count_params(pspecs, cfg)
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
    else:
        tokens = shape.global_batch * (shape.seq_len if shape.kind == "prefill" else 1)
    model_flops = 6.0 * n_active * tokens  # fwd+bwd convention
    # FedZO does (1+b2) forwards and no backward:
    zo_model_flops = 2.0 * n_active * tokens * (1 + b2) if shape.kind == "train" \
        else 2.0 * n_active * tokens

    rec = {
        "arch": arch, "shape": shape_name,
        "mesh": "x".join(str(v) for v in mesh.shape.values()),
        "multi_pod": multi_pod, "algo": algo, "b2": b2,
        "estimator": estimator, "direction_dtype": direction_dtype,
        "donate": donate,
        "n_params": n_params, "n_active_params": n_active,
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "memory": mem,
        "hlo_flops_per_device": flops,
        "hlo_bytes_per_device": bytes_accessed,
        "collective_bytes_per_device": coll, "collective_counts": coll_counts,
        "collective_total_bytes": coll_total,
        "roofline_s": roof,
        "dominant_term": max(roof, key=roof.get),
        "model_flops_total": model_flops,
        "zo_model_flops_total": zo_model_flops,
        "useful_flops_ratio": (zo_model_flops / n_chips) / flops if flops else None,
        "hbm_ok": bool(mem["total_bytes_per_device"] < hw.HBM_PER_CHIP),
    }
    if agg is not None:
        rec["delta_agg_program"] = agg
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b", choices=ARCH_IDS + ("all",))
    ap.add_argument("--shape", default="train_4k", choices=SHAPE_IDS + ("all",))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--algo", default="fedzo", choices=("fedzo", "fedavg"))
    ap.add_argument("--b2", type=int, default=1)
    ap.add_argument("--local-iters", type=int, default=2)
    ap.add_argument("--estimator", default="sphere",
                    choices=("sphere", "gaussian", "coordinate"))
    ap.add_argument("--direction-dtype", default="float32",
                    choices=("float32", "bfloat16"))
    ap.add_argument("--donate", action="store_true",
                    help="donate params/cache buffers (in-place update)")
    ap.add_argument("--out", default="")
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()

    archs = ARCH_IDS if args.arch == "all" else (args.arch,)
    shapes = SHAPE_IDS if args.shape == "all" else (args.shape,)
    existing = set()
    if args.out and args.skip_existing and os.path.exists(args.out):
        with open(args.out) as f:
            for line in f:
                r = json.loads(line)
                existing.add((r["arch"], r["shape"], r["multi_pod"], r["algo"]))

    n_fail = 0
    for arch in archs:
        for shape in shapes:
            key = (arch, shape, args.multi_pod, args.algo)
            if key in existing:
                print(f"skip {key}", flush=True)
                continue
            print(f"=== {arch} × {shape} × "
                  f"{'2x16x16' if args.multi_pod else '16x16'} ({args.algo})",
                  flush=True)
            try:
                rec = run_case(arch, shape, multi_pod=args.multi_pod,
                               algo=args.algo, b2=args.b2, h=args.local_iters,
                               estimator=args.estimator,
                               direction_dtype=args.direction_dtype,
                               donate=args.donate)
            except Exception as e:  # noqa: BLE001 — report and continue
                rec = {"arch": arch, "shape": shape, "multi_pod": args.multi_pod,
                       "algo": args.algo, "error": f"{type(e).__name__}: {e}"}
                n_fail += 1
                print(f"FAIL: {rec['error'][:400]}", flush=True)
            else:
                print(json.dumps({k: rec[k] for k in
                                  ("memory", "hlo_flops_per_device",
                                   "roofline_s", "dominant_term", "hbm_ok",
                                   "compile_s")}, indent=1), flush=True)
            if args.out:
                with open(args.out, "a") as f:
                    f.write(json.dumps(rec) + "\n")
    sys.exit(1 if n_fail else 0)


if __name__ == "__main__":
    main()
