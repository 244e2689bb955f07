"""Reduction of a profiler trace (``.xplane.pb``) to per-layer numbers.

Reads the trace with ``jax.profiler.ProfileData``. Each ``/device:TPU:<i>``
plane's "XLA Ops" line holds the operations the chip ran, with control
flow (``while``, ``conditional``, ``call``) as events that enclose their
bodies' operations. Only leaf events count as work: an event that another
event starts inside of is a container.

- busy: the union of leaf intervals inside the traced window, per chip;
  ``busy_s`` is its mean over the chips.
- kernel and op time: leaf durations summed by base name (the HLO name
  without its ``.N`` suffix, so ``zo_walk.10`` counts as ``zo_walk``),
  summed over the chips.
- exposed collective time: the part of the collectives' intervals (leaf or
  asynchronous all-reduce, all-gather, reduce-scatter, all-to-all,
  collective-permute) during which no other leaf ran on that chip, mean
  over the chips.
- the window: the host span ``bench.window`` that ``run.py`` opens around
  the traced segments, or else the first to the last device event.
- breakdown: the ten ops that took most device time (seconds per chip) and
  the ten longest idle gaps of chip 0, each named by the host span
  (``bench.dispatch`` / ``bench.wait``) open at its midpoint.
"""
from __future__ import annotations

import re
from collections import defaultdict

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")
WINDOW_SPAN = "bench.window"
HOST_SPANS = ("bench.dispatch", "bench.wait")


def base_name(name: str) -> str:
    m = re.match(r"%?([^\s=]+)", name)
    token = m.group(1) if m else name
    return re.sub(r"\.\d+$", "", token)


def is_collective(base: str) -> bool:
    return base.startswith(COLLECTIVES)


def leaves(events):
    """Leaf events of one line: (name, start, end) tuples, with the events
    that enclose another dropped."""
    evs = sorted(events, key=lambda e: (e[1], -e[2]))
    out = []
    for i, (name, s, e) in enumerate(evs):
        if i + 1 < len(evs) and evs[i + 1][1] < e and evs[i + 1][2] <= e:
            continue
        out.append((name, s, e))
    return out


def merge(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def measure(merged):
    return sum(e - s for s, e in merged)


def subtract(a, b):
    """Total length of merged intervals ``a`` not covered by merged ``b``."""
    total, j = 0.0, 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                total += b[k][0] - cur
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            total += e - cur
    return total


def _clip(evs, lo, hi):
    return [(n, max(s, lo), min(e, hi)) for n, s, e in evs
            if e > lo and s < hi]


def reduce_events(devices: dict, host_spans: list) -> dict:
    """``devices``: {plane: {"ops": [(name, start_ns, end_ns)], "async":
    [...]}}; ``host_spans``: [(name, start_ns, end_ns)]. Returns the
    reduction in seconds."""
    win = [(s, e) for n, s, e in host_spans if n == WINDOW_SPAN]
    if win:
        lo, hi = win[0]
    else:
        starts = [s for d in devices.values() for _, s, _ in d["ops"]]
        ends = [e for d in devices.values() for _, _, e in d["ops"]]
        lo, hi = min(starts), max(ends)
    per_dev, ops_total, gaps0 = [], defaultdict(float), []
    for plane in sorted(devices):
        d = devices[plane]
        lv = _clip([(base_name(n), s, e) for n, s, e in leaves(d["ops"])],
                   lo, hi)
        busy = merge([(s, e) for _, s, e in lv])
        ops = defaultdict(float)
        for n, s, e in lv:
            ops[n] += e - s
        coll = merge([(s, e) for n, s, e in lv if is_collective(n)]
                     + [(s, e) for n, s, e in _clip(
                         [(base_name(n), s, e) for n, s, e in d["async"]],
                         lo, hi) if is_collective(n)])
        compute = merge([(s, e) for n, s, e in lv if not is_collective(n)])
        per_dev.append({"plane": plane, "busy_s": measure(busy) * 1e-9,
                        "ops_s": {k: v * 1e-9 for k, v in ops.items()},
                        "collective_s": measure(coll) * 1e-9,
                        "collective_exposed_s":
                            subtract(coll, compute) * 1e-9})
        for k, v in ops.items():
            ops_total[k] += v * 1e-9
        if not gaps0:
            edges = [lo] + [x for iv in busy for x in iv] + [hi]
            gaps0 = [(edges[i], edges[i + 1])
                     for i in range(0, len(edges), 2)
                     if edges[i + 1] > edges[i]]
    n = max(len(per_dev), 1)

    def host_at(t):
        inside = [h for h in host_spans
                  if h[0] in HOST_SPANS and h[1] <= t < h[2]]
        return inside[-1][0] if inside else "no host span"

    gaps = sorted(gaps0, key=lambda g: g[0] - g[1])[:10]
    top = sorted(ops_total.items(), key=lambda kv: -kv[1])[:10]
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": sum(d["busy_s"] for d in per_dev) / n,
        "op_s": dict(ops_total),
        "collective_s": sum(d["collective_s"] for d in per_dev) / n,
        "collective_exposed_s":
            sum(d["collective_exposed_s"] for d in per_dev) / n,
        "devices": per_dev,
        "breakdown": {
            "device_ops": [[k, v / n] for k, v in top],
            "idle_gaps": [[host_at((s + e) / 2), (e - s) * 1e-9]
                          for s, e in gaps]},
    }


def read_trace(path: str):
    """(devices, host_spans) from an ``.xplane.pb``."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {ln.name: ln for ln in plane.lines}
            devices[plane.name] = {
                key: [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                      for e in lines[ln].events] if ln in lines else []
                for key, ln in (("ops", "XLA Ops"),
                                ("async", "Async XLA Ops"))}
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                host.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                            for e in ln.events
                            if e.name == WINDOW_SPAN or e.name in HOST_SPANS)
    return devices, host


def reduce(path: str) -> dict:
    devices, host = read_trace(path)
    if not devices:
        raise RuntimeError(f"{path}: no TPU device plane in the trace")
    return reduce_events(devices, host)
