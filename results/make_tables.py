"""Render the EXPERIMENTS.md §Dry-run / §Roofline tables from the dry-run
jsonl records. Usage: python results/make_tables.py > results/tables.md"""
import glob
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ARCHS = ["rwkv6-7b", "llama-3.2-vision-90b", "deepseek-v3-671b",
         "seamless-m4t-large-v2", "hymba-1.5b", "qwen3-4b", "qwen1.5-32b",
         "gemma-2b", "qwen3-moe-30b-a3b", "qwen2-0.5b"]
SHAPES = ["train_4k", "prefill_32k", "decode_32k", "long_500k"]


def load():
    best = {}
    for f in sorted(glob.glob(os.path.join(HERE, "dryrun_*.jsonl"))):
        for line in open(f):
            r = json.loads(line)
            k = (r["arch"], r["shape"], r["multi_pod"])
            if "error" not in r:
                best[k] = r  # last ok record wins
            elif k not in best:
                best[k] = r
    return best


def gib(b):
    return f"{b/2**30:.1f}"


def s3(x):
    return f"{x:.4f}" if x >= 1e-4 else f"{x:.2e}"


def main():
    best = load()
    print("### Dry-run matrix (compile status, per-device memory)\n")
    print("| arch | shape | 16×16 mem GiB (fits?) | 2×16×16 mem GiB (fits?) |")
    print("|---|---|---|---|")
    for a in ARCHS:
        for sh in SHAPES:
            cells = []
            for mp in (False, True):
                r = best.get((a, sh, mp))
                if r is None:
                    cells.append("—")
                elif "error" in r:
                    cells.append("FAIL")
                else:
                    m = r["memory"]["total_bytes_per_device"]
                    cells.append(f"{gib(m)} ({'✓' if r['hbm_ok'] else '✗'})")
            print(f"| {a} | {sh} | {cells[0]} | {cells[1]} |")

    print("\n### Roofline (single-pod 16×16, per-chip; v5e constants)\n")
    print("`cost_analysis` counts scan/while bodies once, so HLO FLOPs/bytes"
          " under-count by the layer trip count. We correct with"
          " κ = max(1, analytic_ZO_FLOPs / HLO_FLOPs): compute uses the"
          " analytic count directly; memory bytes are scaled by κ (layer"
          " bytes scale with layer flops); collectives are trip-count-"
          "weighted at parse time and need no correction.\n")
    print("| arch | shape | compute s | memory s (κ-adj) | collective s | "
          "dominant | κ |")
    print("|---|---|---|---|---|---|---|")
    for a in ARCHS:
        for sh in SHAPES:
            r = best.get((a, sh, False))
            if r is None or "error" in r:
                print(f"| {a} | {sh} | FAIL | | | | |")
                continue
            ro = r["roofline_s"]
            hlo = r["hlo_flops_per_device"]
            analytic = r["zo_model_flops_total"] / 256
            kappa = max(1.0, analytic / hlo) if hlo else 1.0
            comp = max(analytic, hlo) / 197e12
            mem = ro["memory_s"] * kappa
            coll = ro["collective_s"]
            dom = {"compute": comp, "memory": mem, "collective": coll}
            name = max(dom, key=dom.get)
            print(f"| {a} | {sh} | {s3(comp)} | {s3(mem)} | {s3(coll)} | "
                  f"**{name}** | {kappa:.1f} |")

    print("\n### Collective breakdown (single-pod, trip-count-weighted "
          "GiB/device)\n")
    print("| arch | shape | all-reduce | all-gather | reduce-scatter | "
          "all-to-all | permute |")
    print("|---|---|---|---|---|---|---|")
    for a in ARCHS:
        for sh in SHAPES:
            r = best.get((a, sh, False))
            if r is None or "error" in r:
                continue
            c = r["collective_bytes_per_device"]
            print(f"| {a} | {sh} | {gib(c['all-reduce'])} | "
                  f"{gib(c['all-gather'])} | {gib(c['reduce-scatter'])} | "
                  f"{gib(c['all-to-all'])} | {gib(c['collective-permute'])} |")

    print("\n### Multi-pod (2×16×16): round program + dense-uplink "
          "aggregation program\n")
    print("| arch | shape | round coll GiB/dev | agg-program coll GiB/dev | "
          "mem GiB (fits?) |")
    print("|---|---|---|---|---|")
    for a in ARCHS:
        for sh in SHAPES:
            r = best.get((a, sh, True))
            if r is None or "error" in r:
                continue
            c = sum(r["collective_bytes_per_device"].values())
            agg = r.get("delta_agg_program")
            ac = gib(agg["collective_total_bytes"]) if agg else "—"
            m = r["memory"]["total_bytes_per_device"]
            print(f"| {a} | {sh} | {gib(c)} | {ac} | "
                  f"{gib(m)} ({'✓' if r['hbm_ok'] else '✗'}) |")


if __name__ == "__main__":
    main()
