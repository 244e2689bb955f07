"""The device time of a profiler trace (``.xplane.pb``) split by the
program's named scopes (``repro.obs.trace.SCOPES``).

Each op a TPU plane's "XLA Ops" line holds has event metadata, and there
a ``tf_op`` stat: the op's ``op_name`` path from the compiled program,
with every ``jax.named_scope`` it runs under
(``jit(fn)/while/body/closed_call/vmap(fedzo.local)/fedzo.query/dot``).
``jax.profiler.ProfileData`` does not expose event metadata, so
``op_paths`` reads it from the protobuf's wire format. The leaf ops and
the window are those of ``trace_reduce`` (leaf events clipped to the
harness's ``bench.window`` span), so the scopes under no other scope and
``"unscoped"`` partition ``trace_reduce``'s busy time.

The per-layer readers under ``bench/metrics/`` call ``scope_share``. It
reads the trace ``run.py`` leaves in ``.bench_trace/`` and returns None
for a program that registers or compiles no scopes.
"""
from __future__ import annotations

import glob
import os
import re
from pathlib import Path

import trace_reduce as tr

UNSCOPED = "unscoped"
TRACE_DIR = Path(__file__).resolve().parents[1] / ".bench_trace"
TPU_PLANE = "/device:TPU:"
PATH_STAT = "tf_op"


def program_scopes() -> tuple:
    """The named scopes the program registers, or none for a program
    that has no registry."""
    try:
        from repro.obs.trace import SCOPES
    except ImportError:
        return ()
    return tuple(SCOPES)


def _varint(buf, i):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf, lo, hi):
    """(field number, value) of the message in ``buf[lo:hi]``: an int for
    a varint, (start, end) for a length-delimited field; fixed-width
    fields are skipped."""
    i = lo
    while i < hi:
        key, i = _varint(buf, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            v, i = _varint(buf, i)
            yield num, v
        elif wire == 2:
            n, i = _varint(buf, i)
            yield num, (i, i + n)
            i += n
        elif wire == 1:
            i += 8
        elif wire == 5:
            i += 4
        else:
            raise ValueError(f"wire type {wire} at byte {i}")


def _str(buf, span):
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _map_value(buf, span):
    """(key, value span) of one map<int64, message> entry."""
    key, val = 0, (0, 0)
    for num, v in _fields(buf, *span):
        if num == 1:
            key = v
        elif num == 2:
            val = v
    return key, val


def _plane_paths(buf, lo, hi):
    """(plane name, {op event name: ``tf_op`` path}) of one XPlane."""
    name, events, stat_names = "", [], {}
    for num, v in _fields(buf, lo, hi):
        if num == 2:
            name = _str(buf, v)
            if not name.startswith(TPU_PLANE):
                return name, {}
        elif num == 4:                    # event_metadata
            events.append(_map_value(buf, v)[1])
        elif num == 5:                    # stat_metadata
            key, span = _map_value(buf, v)
            stat_names[key] = next((_str(buf, s) for n, s in
                                    _fields(buf, *span) if n == 2), "")
    out = {}
    for span in events:
        ev_name, stats = "", []
        for num, v in _fields(buf, *span):
            if num == 2:
                ev_name = _str(buf, v)
            elif num == 5:
                stats.append(dict(_fields(buf, *v)))
        for st in stats:
            if stat_names.get(st.get(1)) != PATH_STAT:
                continue
            if 5 in st:                   # str_value
                out[ev_name] = _str(buf, st[5])
            elif 7 in st:                 # ref_value: a stat_metadata name
                out[ev_name] = stat_names.get(st[7], "")
    return name, out


def op_paths(path: str) -> dict:
    """{TPU plane: {op event name: its ``op_name`` path}} from an
    ``.xplane.pb``."""
    buf = memoryview(Path(path).read_bytes())
    out = {}
    for num, v in _fields(buf, 0, len(buf)):
        if num == 1:                      # XSpace.planes
            name, paths = _plane_paths(buf, *v)
            if name.startswith(TPU_PLANE):
                out[name] = paths
    return out


def scopes_on(op_path: str, names) -> list:
    """The registered scopes on an ``op_name`` path. A scope inside a
    transformation reads as ``vmap(fedzo.local)``, so the path is split on
    ``/``, parentheses and the ``:`` before the op type."""
    return sorted({p for p in re.split(r"[/():]", op_path) if p in names})


def scope_seconds(devices: dict, host_spans: list, paths: dict,
                  names) -> dict:
    """{scope: device seconds of the leaf ops under it, summed over the
    chips and inclusive of nested scopes, plus ``"unscoped"``}.
    ``devices`` and ``host_spans`` are ``trace_reduce.read_trace``'s;
    ``paths`` is ``op_paths``'s. An op is found by its full event name."""
    win = [(s, e) for n, s, e in host_spans if n == tr.WINDOW_SPAN]
    if win:
        lo, hi = win[0]
    else:
        lo = min(s for d in devices.values() for _, s, _ in d["ops"])
        hi = max(e for d in devices.values() for _, _, e in d["ops"])
    total = dict.fromkeys([UNSCOPED, *names], 0.0)
    for plane, d in devices.items():
        plane_paths = paths.get(plane, {})
        for n, s, e in tr._clip(tr.leaves(d["ops"]), lo, hi):
            for sc in scopes_on(plane_paths.get(n, ""), names) or [UNSCOPED]:
                total[sc] += (e - s) * 1e-9
    return total


_CACHE = {}


def trace_scope_seconds(path: str, names) -> dict:
    """``scope_seconds`` of a trace file, kept while the file is unchanged
    (each reader asks for the same split)."""
    key = (str(path), os.stat(path).st_mtime_ns, tuple(names))
    if key not in _CACHE:
        devices, host = tr.read_trace(str(path))
        _CACHE.clear()
        _CACHE[key] = scope_seconds(devices, host, op_paths(path), names)
    return _CACHE[key]


def latest_trace(trace_dir: Path = TRACE_DIR):
    found = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    return max(found, key=os.path.getmtime) if found else None


def scope_share(ctx, scope: str):
    """A scope's device seconds as a share (%) of chips x window, or None
    when there is no trace, the program registers no scopes, or none of
    them is on the trace's ops. ``scope=UNSCOPED`` reads the ops under no
    scope. The trace is ``ctx["trace_path"]`` where given, else the
    newest under ``.bench_trace/``."""
    names = program_scopes()
    path = ctx.get("trace_path") or latest_trace()
    if not names or path is None or ctx["window_s"] <= 0:
        return None
    try:
        got = trace_scope_seconds(path, names)
    except (OSError, RuntimeError, ValueError, IndexError):
        return None
    if not any(got[s] > 0 for s in names):
        return None
    return 100.0 * got.get(scope, 0.0) / ctx["chips"] / ctx["window_s"]
