"""The :class:`Telemetry` hub: simulated-clock events and wall-clock
program spans.

Two clocks, kept apart:

- **Simulated events.** The serving engine charges a *simulated* clock
  (``engine.sim_time``) with the planner's model of each phase; those
  charges are recorded as events with
  ``tel.emit_span(name, start, dur, track=..., **args)`` (a phase whose
  charge is known only after the fact, e.g. the decode charge
  ``cost_mx.max(axis=1).sum()``) and ``tel.instant(name, **args)``.
  Every span/instant becomes one structured event dict (the JSONL schema
  in :mod:`repro.telemetry.export`); ``track`` names the timeline it
  renders on in the Chrome trace ("engine", "device0".."deviceG-1").
- **Program spans.** ``with tel.span(name, **args):`` times the host
  work itself on the wall clock, as a ``jax.profiler.TraceAnnotation``:
  it lands in the profiler's trace beside the device's operations, its
  keyword args as event stats, and costs about a microsecond when no
  profiler is attached. ``tel.step_span`` marks one engine step for the
  profiler's step view. Program spans are never appended to ``events``.

:class:`Telemetry` is the object the planes hold. It is **always
constructed** — ``ServingEngine(..., telemetry=None)`` gets a disabled
instance — because the metrics registry doubles as the single source of
truth for read-through attributes (``jit_trace_counts``,
``migration_records``) that must keep working with telemetry off.
Only *event recording* (spans/instants, the export surface) is gated by
``enabled``; registry instruments and program spans are pure host-side
state and can never perturb tokens.
"""
from __future__ import annotations

from typing import Callable

import jax

from .registry import Registry

__all__ = ["Telemetry"]


class Telemetry:
    """Per-run telemetry hub: registry + event log + simulated clock,
    and the program's wall-clock spans."""

    def __init__(self, *, enabled: bool = True,
                 clock: Callable[[], float] | None = None):
        self.enabled = enabled
        self.registry = Registry()
        self.events: list[dict] = []
        # Structured per-migration records (the engine's old ad-hoc
        # ``migration_records`` list now lives here; the engine attribute
        # is a read-through). Always recorded — callers introspect these
        # regardless of event tracing.
        self.migration_records: list[dict] = []
        self._clock = clock if clock is not None else (lambda: 0.0)

    # -- clock ---------------------------------------------------------
    def set_clock(self, clock: Callable[[], float]) -> None:
        """Bind the simulated-time source (e.g. ``lambda: engine.sim_time``)."""
        self._clock = clock

    def now(self) -> float:
        return float(self._clock())

    # -- registry passthrough ------------------------------------------
    def counter(self, name: str):
        return self.registry.counter(name)

    def gauge(self, name: str):
        return self.registry.gauge(name)

    def histogram(self, name: str, boundaries=None):
        return self.registry.histogram(name, boundaries)

    # -- events --------------------------------------------------------
    def emit_span(self, name: str, start: float, dur: float, *,
                  track: str = "engine", **args) -> None:
        """Record a completed span ``[start, start+dur)`` on ``track``."""
        if not self.enabled:
            return
        ev = {"kind": "span", "name": name, "track": track,
              "ts": float(start), "dur": float(dur)}
        if args:
            ev["args"] = args
        self.events.append(ev)

    def instant(self, name: str, *, track: str = "engine",
                ts: float | None = None, **args) -> None:
        """Record a zero-duration marker (preemption, drift fire, ...)."""
        if not self.enabled:
            return
        ev = {"kind": "instant", "name": name, "track": track,
              "ts": self.now() if ts is None else float(ts)}
        if args:
            ev["args"] = args
        self.events.append(ev)

    def record_migration(self, record: dict) -> None:
        """Append one structured migration record (always, even when
        event tracing is off) and mirror it as an instant event."""
        self.migration_records.append(record)
        self.instant("migration", ts=record.get("sim_time"),
                     **{k: v for k, v in record.items() if k != "sim_time"})

    # -- program spans (wall clock) -----------------------------------
    @staticmethod
    def span(name: str, **args) -> jax.profiler.TraceAnnotation:
        """Wall-clock program span: ``with tel.span("engine.regret",
        step=7):`` records ``name`` with ``args`` in the profiler's trace
        while one is being taken, and nothing otherwise."""
        return jax.profiler.TraceAnnotation(name, **args)

    @staticmethod
    def step_span(name: str, step: int, **args
                  ) -> jax.profiler.StepTraceAnnotation:
        """The program span of one whole step, marked for the profiler's
        step view (``step_num``) and carrying ``step`` like every other
        program span."""
        return jax.profiler.StepTraceAnnotation(
            name, step_num=step, step=step, **args
        )
