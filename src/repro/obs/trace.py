"""Trace spans + profiling hooks (DESIGN.md §14).

A ``Tracer`` records a tree of wall-clock spans around the phases of a run
— ``compile`` (jit lowering + XLA compile, via the AOT ``lower().compile()``
path), ``execute``/``segment`` (device time of the compiled program),
``eval``, whatever the driver opens. Spans nest: the tracer keeps a stack,
every span records its depth and parent, and ``report()`` renders the tree.

The point is separating COMPILE time from EXECUTE time: a multi-thousand-
round engine run spends seconds in XLA before the first round executes, and
without spans that cost silently pollutes rounds/s numbers. Drivers that
take a ``tracer=`` (``sim.run_experiment``, ``sim.run_sweep``,
``FedServer.run``) compile through ``timed_compile`` so each static shape
reports exactly one ``compile`` span per program cache (the checkpointed
segment runner compiles once per chunk size and reuses the executable
across segments).

``Tracer(profile_dir=...)`` additionally wraps the run in a
``jax.profiler`` trace (one ``start_trace``/``stop_trace`` pair), so the
same handle that gives coarse spans can drop a full XLA profile for
perfetto/tensorboard when you need the microscope.
"""
from __future__ import annotations

import time
from contextlib import contextmanager
from dataclasses import dataclass, field
from typing import Optional


@dataclass
class Span:
    name: str
    start: float
    duration: float = 0.0
    depth: int = 0
    parent: Optional[int] = None   # index into Tracer.spans
    meta: dict = field(default_factory=dict)


class Tracer:
    """Hierarchical wall-clock span recorder + optional jax.profiler hook.

    Cheap enough to always pass: an un-entered tracer costs one attribute
    check per driver call. Not thread-safe — one tracer per driver.
    """

    def __init__(self, profile_dir: Optional[str] = None):
        self.spans: list = []
        self.profile_dir = profile_dir
        self._stack: list = []       # indices of open spans
        self._compiled: dict = {}    # static-shape key -> compiled program

    @contextmanager
    def span(self, name: str, **meta):
        idx = len(self.spans)
        s = Span(name=name, start=time.perf_counter(),
                 depth=len(self._stack),
                 parent=self._stack[-1] if self._stack else None,
                 meta=dict(meta))
        self.spans.append(s)
        self._stack.append(idx)
        try:
            yield s
        finally:
            s.duration = time.perf_counter() - s.start
            self._stack.pop()

    @contextmanager
    def profile(self):
        """Wrap a block in a jax.profiler trace when ``profile_dir`` is
        set; a plain no-op otherwise."""
        if not self.profile_dir:
            yield
            return
        import jax.profiler
        jax.profiler.start_trace(self.profile_dir)
        try:
            with self.span("jax_profile", trace_dir=self.profile_dir):
                yield
        finally:
            jax.profiler.stop_trace()

    # -- compile/execute separation ------------------------------------------
    def timed_compile(self, key, jitted, *args):
        """AOT-compile ``jitted`` for ``args`` under a ``compile`` span,
        ONCE per static-shape ``key``: repeat calls with the same key reuse
        the cached executable and record no new compile span. Returns the
        compiled program (call it with the same arg structure)."""
        if key not in self._compiled:
            with self.span("compile", key=str(key)):
                self._compiled[key] = jitted.lower(*args).compile()
        return self._compiled[key]

    def executables(self) -> list:
        """The compiled programs, one per static-shape key. Their
        ``as_text()`` is what the backend compiler emitted (on a TPU, a
        compiled Pallas kernel shows up as ``tpu_custom_call``)."""
        return list(self._compiled.values())

    def invalidate_compiled(self, key=None):
        """Drop cached executables (all, or one key) — the divergence-
        rollback path re-bakes the backed-off lr into a new program."""
        if key is None:
            self._compiled.clear()
        else:
            self._compiled.pop(key, None)

    # -- reporting -----------------------------------------------------------
    def named(self, name: str) -> list:
        return [s for s in self.spans if s.name == name]

    def total(self, name: str) -> float:
        """Summed seconds across all spans of one name."""
        return sum(s.duration for s in self.named(name))

    def totals(self) -> dict:
        out: dict = {}
        for s in self.spans:
            agg = out.setdefault(s.name, {"count": 0, "seconds": 0.0})
            agg["count"] += 1
            agg["seconds"] += s.duration
        return out

    def report(self) -> str:
        """The span tree as indented text, one line per span."""
        lines = []
        for s in self.spans:
            meta = (" " + " ".join(f"{k}={v}" for k, v in s.meta.items())
                    if s.meta else "")
            lines.append(f"{'  ' * s.depth}{s.name}: "
                         f"{s.duration * 1e3:.2f} ms{meta}")
        return "\n".join(lines)
