"""repro.obs — unified telemetry: spans, metrics, run events, cost loop.

One :class:`Telemetry` object bundles the four observability primitives the
stack publishes into:

  * a span :class:`~repro.obs.trace.Tracer` (compile/chunk/checkpoint/
    publish phases, Chrome ``trace.json`` export; every span, enabled or
    not, is also a ``jax.profiler`` annotation that a running profiler
    records);
  * a thread-safe :class:`~repro.obs.metrics.MetricsRegistry` (eps burn,
    rounds/sec, serve counters, fault connectivity);
  * an optional JSONL :class:`~repro.obs.events.EventLog` run-event stream
    (rendered by ``python -m repro.launch.obs report``);
  * the optional predicted-vs-measured :mod:`~repro.obs.cost` loop, plus an
    opt-in ``jax.profiler`` device-trace capture.

Telemetry is OFF by default and ambient: `repro.api.run`, `repro.sweep`
and `repro.serve` consult :func:`active` and do nothing unless a caller
has installed an enabled instance with :func:`enable` (or passed ``obs=``
explicitly). Telemetry never touches device math — a run with it on is
bit-identical to one with it off, and CI gates that (``obs_off_identical``
in BENCH_obs.json) along with the overhead ceiling (``overhead_ratio``).

>>> import repro.obs as obs
>>> obs.active().enabled                   # ambient default: off
False
>>> tel = obs.Telemetry()
>>> with tel.span("phase", k=1):
...     tel.metrics.counter("demo.count").inc()
>>> tel.tracer.summary()["phase"]["count"]
1
>>> tel.metrics.snapshot()["demo.count"]
1
>>> prev = obs.enable()                    # install ambient telemetry...
>>> obs.active().enabled
True
>>> obs.disable()                          # ...and restore the default
>>> obs.active().enabled
False
"""
from __future__ import annotations

import contextlib
import uuid

from repro.obs.cost import ChunkCost, CostModel, analyze_chunk, calibrate
from repro.obs.events import (DEFAULT_EVENTS_PATH, EventLog, group_runs,
                              read_events)
from repro.obs.metrics import Counter, Gauge, Histogram, MetricsRegistry
from repro.obs.trace import NULL_SPAN, Span, Tracer

__all__ = [
    "Telemetry", "enable", "disable", "active",
    "Tracer", "Span", "MetricsRegistry", "Counter", "Gauge", "Histogram",
    "EventLog", "read_events", "group_runs", "DEFAULT_EVENTS_PATH",
    "CostModel", "ChunkCost", "analyze_chunk", "calibrate",
]


class Telemetry:
    """One run-scoped (or process-scoped) telemetry bundle.

    enabled:      master switch — False makes every hook a no-op (this is
                  the ambient default the bit-identity gate pins).
    events:       an :class:`EventLog`, a path for one, or None (no stream).
    cost:         True turns on the predicted-vs-measured chunk-cost loop
                  (one extra lower/compile per chunk program, outside the
                  timed region).
    cost_model:   pin the roofline peaks instead of calibrating.
    profile_dir:  opt-in ``jax.profiler`` device-trace capture directory —
                  the runner wraps its chunk loop in
                  ``jax.profiler.trace(profile_dir)``.
    """

    def __init__(self, *, enabled: bool = True,
                 events: "EventLog | str | None" = None,
                 cost: bool = False, cost_model: CostModel | None = None,
                 profile_dir: str | None = None,
                 max_spans: int = 1_000_000):
        self.enabled = enabled
        self.tracer = Tracer(enabled=enabled, max_spans=max_spans)
        self.metrics = MetricsRegistry()
        if isinstance(events, str):
            events = EventLog(events)
        self.events = events if enabled else None
        self.cost_enabled = bool(cost) and enabled
        self.cost_model = cost_model
        self.profile_dir = profile_dir if enabled else None

    # -- hooks the instrumented code calls ----------------------------------

    def span(self, name: str, **args):
        """Timed region (no-op when disabled) — see `Tracer.span`."""
        return self.tracer.span(name, **args)

    def emit(self, event: str, **fields) -> None:
        """Append one run event to the JSONL stream (no-op without one)."""
        if self.events is not None:
            self.events.emit(event, **fields)

    def profile(self):
        """Context manager capturing a ``jax.profiler`` device trace into
        ``profile_dir`` (no-op when unset). A profiler that fails raises:
        a run asked to trace never silently runs untraced."""
        if not self.profile_dir:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.trace(self.profile_dir)

    @staticmethod
    def new_run_id() -> str:
        """8-hex token grouping one run's events."""
        return uuid.uuid4().hex[:8]

    # -- introspection ------------------------------------------------------

    def export_chrome(self, path: str) -> str:
        return self.tracer.export_chrome(path)

    def summary(self) -> dict:
        return {"enabled": self.enabled,
                "spans": self.tracer.summary(),
                "metrics": self.metrics.snapshot()}

    def close(self) -> None:
        if self.events is not None:
            self.events.close()


_DISABLED = Telemetry(enabled=False)
_active: Telemetry = _DISABLED


def active() -> Telemetry:
    """The ambient Telemetry (a shared disabled instance by default)."""
    return _active


def enable(**kwargs) -> Telemetry:
    """Install (and return) an enabled ambient Telemetry; kwargs as for
    :class:`Telemetry`. The previous instance is replaced, not stacked."""
    global _active
    _active = Telemetry(enabled=True, **kwargs)
    return _active


def disable() -> None:
    """Restore the disabled ambient default (closes an open event stream)."""
    global _active
    if _active is not _DISABLED:
        _active.close()
    _active = _DISABLED
