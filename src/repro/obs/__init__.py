"""Serve-path observability: metrics registry + span tracer + latency.

One bundle (DESIGN.md §10) threads through the serve engine and its
front end, the jitted step constructors, the paged KV pool, and the
train loop:

* ``Observability.metrics`` — the labeled counter/gauge/histogram sink
  (obs/metrics.py) absorbing the previously-scattered telemetry:
  per-plan ``sched/*`` stats at retirement, ``PagedKVCache.stats()``
  per step, admission/drop counts, quantized expert payload bytes.
* ``Observability.tracer`` — spans of the step timeline (obs/trace.py):
  the front end's poll / scheduling pass / admit / prefix probe / step /
  assemble / input transfers / forward dispatch / host sync /
  postprocess / streaming / prefix registration / retire, plus instants
  for recompiles, slow steps, block evictions and compactions.  The
  spans are kept as Chrome-trace JSON and, while a ``jax.profiler``
  trace runs, also land in that profile beside the device's programs.
* ``Observability.straggler`` — the PR 2 ``StragglerMonitor`` wired as
  a serve-side slow-step detector (injectable clock): flagged steps
  become ``serve/slow_steps`` counts and ``slow_step`` trace instants.
* per-request latency accounting (obs/latency.py) is ALWAYS on — it is
  a handful of host clock reads per step and fills ``Request.stats``
  ``lat/*`` whether or not a sink is attached.

The default is ``NOOP`` — null sinks whose methods are empty, so
instrumented code never branches and the off-path costs nothing.
Tracing adds NO device-side ops anywhere (host wall-clock and already-
materialized host values only): greedy tokens are bitwise-identical
with observability on or off, asserted in tests/test_obs.py.

Following the PR 1/2/4 registry idiom, sinks are registered by name
(``null`` and ``memory`` ship built-in) so launchers select one by flag.
"""
from __future__ import annotations

import time
from typing import Callable, Dict, Optional

from repro.obs.latency import (LAT_KEYS, RequestTimeline, aggregate,
                               drop_summary, latency_summary)
from repro.obs.metrics import (NULL_METRICS, MetricsRegistry, NullMetrics,
                               percentile, summarize)
from repro.obs.trace import (NULL_TRACER, NullTracer, SpanTracer,
                             device_trace, validate_chrome_trace)

__all__ = [
    "Observability", "NOOP", "MetricsRegistry", "NullMetrics",
    "SpanTracer", "NullTracer", "RequestTimeline", "LAT_KEYS",
    "aggregate", "drop_summary", "latency_summary", "percentile",
    "summarize",
    "device_trace", "validate_chrome_trace", "register_sink", "get_sink",
    "available_sinks", "NULL_METRICS", "NULL_TRACER",
]


class Observability:
    """Metrics + tracer + optional straggler monitor, one shared clock.

    ``metered`` is True when the metrics sink is a real one: call sites
    that would do real work to FEED it (walking a params tree for byte
    counts, reading the pool's stats, converting a stats dict) gate on
    it, so a bundle that only traces does none of that work; plain
    span/counter calls do not gate — the null sinks absorb those for
    free."""

    def __init__(self, metrics: Optional[MetricsRegistry] = None,
                 tracer: Optional[SpanTracer] = None,
                 straggler=None,
                 clock: Callable[[], float] = time.perf_counter):
        self.metrics = NULL_METRICS if metrics is None else metrics
        self.tracer = NULL_TRACER if tracer is None else tracer
        self.straggler = straggler
        self.clock = clock
        self.metered = self.metrics is not NULL_METRICS

    @classmethod
    def memory(cls, clock: Callable[[], float] = time.perf_counter,
               straggler_window: int = 32, straggler_factor: float = 2.0):
        """The full in-memory bundle: fresh registry + tracer + straggler
        monitor on one injectable clock (tests drive a virtual clock)."""
        from repro.runtime.fault import StragglerMonitor
        return cls(metrics=MetricsRegistry(),
                   tracer=SpanTracer(clock=clock),
                   straggler=StragglerMonitor(window=straggler_window,
                                              factor=straggler_factor,
                                              clock=clock),
                   clock=clock)

    # -- step bracket (engine/train loops) -----------------------------
    def step_begin(self, step: int) -> None:
        if self.straggler is not None:
            self.straggler.start_step(step)

    def step_end(self, step: int, *, scope: str = "serve") -> None:
        """Close the straggler window for ``step``; a flagged step (>
        factor x rolling median) becomes a ``<scope>/slow_steps`` count
        and a ``slow_step`` trace instant."""
        if self.straggler is None:
            return
        flag = self.straggler.end_step()
        if flag:
            self.metrics.inc(f"{scope}/slow_steps")
            self.tracer.instant(
                "slow_step", scope=scope, step=flag["step"],
                duration_s=flag["duration"],
                slowdown=round(flag["slowdown"], 3))

    # -- trace-time hooks ----------------------------------------------
    def on_trace(self, kind: str, **static) -> None:
        """Recompile-event detection: called from INSIDE a jitted step
        body, which python-executes only while jax traces — i.e. exactly
        once per distinct input shape.  Host-side only; adds no ops to
        the traced computation."""
        self.metrics.inc("serve/recompiles", kind=kind)
        self.tracer.instant("recompile", kind=kind, **static)


NOOP = Observability()


# ----------------------------------------------------------------------
# Sink registry (PR 1/2/4 idiom): name -> Observability factory
# ----------------------------------------------------------------------
_SINKS: Dict[str, Callable[..., Observability]] = {}


def register_sink(name: str):
    def deco(fn: Callable[..., Observability]):
        _SINKS[name] = fn
        return fn
    return deco


def get_sink(name: str, **kw) -> Observability:
    if name not in _SINKS:
        raise ValueError(f"unknown observability sink {name!r}; "
                         f"registered: {available_sinks()}")
    return _SINKS[name](**kw)


def available_sinks():
    return sorted(_SINKS)


@register_sink("null")
def _null_sink(**kw) -> Observability:
    return NOOP


@register_sink("memory")
def _memory_sink(**kw) -> Observability:
    return Observability.memory(**kw)
