"""repro.obs — the observability substrate (DESIGN.md §14).

- ``sinks``  — MetricsSink protocol + JSONL / CSV / memory / fan-out sinks.
- ``taps``   — RoundTap: the host half of the engine's in-scan
  ``io_callback`` telemetry stream (opt-in ``tap_every=k``).
- ``trace``  — Tracer/span layer separating compile from execute time,
  each span also a profiler annotation, with an optional ``jax.profiler``
  trace-dir hook; ``SCOPES``/``scope`` name the round's layers inside the
  compiled program.
- ``ledger`` — CommsLedger: unified per-round wire/dense byte accounting
  and cumulative uplink/downlink totals for ``history()`` rows.
- ``manifest`` — run manifests (config hash, strategy, versions, git sha,
  topology, fault/divergence event stream) alongside checkpoints/results.
"""
from __future__ import annotations

from repro.obs.ledger import CommsLedger
from repro.obs.manifest import (MANIFEST_NAME, build_manifest, git_sha,
                                read_manifest, write_manifest)
from repro.obs.sinks import (CsvSink, JsonlSink, MemorySink, MetricsSink,
                             MultiSink, NullSink, read_jsonl)
from repro.obs.taps import RoundTap
from repro.obs.trace import SCOPES, Span, Tracer, scope

__all__ = [
    "CommsLedger",
    "MANIFEST_NAME", "build_manifest", "git_sha", "read_manifest",
    "write_manifest",
    "CsvSink", "JsonlSink", "MemorySink", "MetricsSink", "MultiSink",
    "NullSink", "read_jsonl",
    "RoundTap",
    "SCOPES", "Span", "Tracer", "scope",
]
