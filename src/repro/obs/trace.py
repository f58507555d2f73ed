"""Step-timeline span tracer: Chrome-trace JSON and the profiler's trace.

``SpanTracer`` records HOST-side wall-clock spans of the serve/train
loops — the front end's poll, the scheduling pass, admission, prefix-hash
probe, step assembly and its input transfers, the jitted forward
dispatch, the one-per-step host sync, postprocessing, token streaming,
prefix registration, retirement — plus instant events for the things
that happen *to* the loop: recompiles (first call at a new shape),
straggler-flagged slow steps, block evictions/compactions.  The in-memory
artifact (``results/trace/*.json``) loads directly in ``chrome://tracing``
/ https://ui.perfetto.dev.

Every span is also a ``jax.profiler.TraceAnnotation`` of the same name
and arguments (the step span a ``StepTraceAnnotation`` with its
``step_num``) for as long as it is open.  While a profiler trace runs
(``device_trace()``, ``launch/serve --device-trace``), the engine's spans
therefore land in the profile on the device's own clock, beside the
device's programs, and each idle gap of the device can be charged to the
host work around it.  With no profiler running an annotation is a
no-op of about a microsecond.

Overhead contract (DESIGN.md §10): a span is two ``clock()`` calls, one
annotation and one dict append; nothing here touches a device value,
inserts an op into a traced computation, or forces a sync — the forward
span measures DISPATCH cost (jax is async), the host_sync span measures
where blocking actually happens.  Greedy tokens are asserted
bitwise-identical with tracing on vs off (tests/test_obs.py).  The
default sink is ``NullTracer`` (shared no-op context manager, no state,
no annotation).

``device_trace()`` is the bracket around ``jax.profiler.start_trace`` /
``stop_trace`` that writes that profile; a profiler failure fails the
run.
"""
from __future__ import annotations

import contextlib
import json
import time
from typing import Callable, List, Optional

from jax.profiler import StepTraceAnnotation, TraceAnnotation


class _Span:
    """Context manager for one complete ("ph": "X") event, mirrored into
    the profiler's trace by ``annotation`` while it is open."""
    __slots__ = ("tracer", "name", "args", "annotation", "t0")

    def __init__(self, tracer, name, args, annotation):
        self.tracer = tracer
        self.name = name
        self.args = args
        self.annotation = annotation

    def __enter__(self):
        self.t0 = self.tracer.clock()
        self.annotation.__enter__()
        return self

    def __exit__(self, *exc):
        self.annotation.__exit__(*exc)
        t1 = self.tracer.clock()
        self.tracer._emit(self.name, "X", self.t0, dur=t1 - self.t0,
                          args=self.args)
        return False


class SpanTracer:
    """Chrome-trace event collector (host-side spans + instants)."""

    def __init__(self, clock: Callable[[], float] = time.perf_counter,
                 process_name: str = "repro-serve"):
        self.clock = clock
        self.process_name = process_name
        self._t_origin = clock()
        self.events: List[dict] = []

    # -- recording -----------------------------------------------------
    def _us(self, t: float) -> float:
        return (t - self._t_origin) * 1e6

    def _emit(self, name: str, ph: str, t: float, *, dur: float = None,
              args: Optional[dict] = None) -> None:
        ev = {"name": name, "ph": ph, "ts": self._us(t),
              "pid": 0, "tid": 0}
        if dur is not None:
            ev["dur"] = dur * 1e6
        if ph == "i":
            ev["s"] = "t"                       # thread-scoped instant
        if args:
            ev["args"] = {k: (v if isinstance(v, (int, float, str, bool))
                              else repr(v)) for k, v in args.items()}
        self.events.append(ev)

    def span(self, name: str, **args) -> _Span:
        """``with tracer.span("serve/forward", tokens=T): ...``"""
        return _Span(self, name, args or None, TraceAnnotation(name, **args))

    def step(self, name: str, step: int, **args) -> _Span:
        """The span of one whole loop step: recorded with ``step`` among
        its arguments, and a ``StepTraceAnnotation`` (``step_num``) in
        the profiler's trace, which groups the profile by step."""
        return _Span(self, name, {"step": step, **args},
                     StepTraceAnnotation(name, step_num=step, **args))

    def instant(self, name: str, **args) -> None:
        self._emit(name, "i", self.clock(), args=args or None)

    # -- export --------------------------------------------------------
    def to_chrome_trace(self) -> dict:
        """Events sorted by timestamp (viewers require monotone order
        within a track) under the standard ``traceEvents`` envelope."""
        meta = [{"name": "process_name", "ph": "M", "pid": 0, "tid": 0,
                 "args": {"name": self.process_name}}]
        return {"traceEvents":
                meta + sorted(self.events, key=lambda e: e["ts"]),
                "displayTimeUnit": "ms"}

    def save(self, path) -> str:
        import pathlib
        p = pathlib.Path(path)
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(json.dumps(self.to_chrome_trace(), indent=1))
        return str(p)


class NullTracer(SpanTracer):
    """Default sink: ``span()``/``step()`` hand back one shared do-nothing
    context manager and ``instant``/``save`` are empty — no clock reads,
    no annotation, no allocation, no file."""

    def __init__(self):
        super().__init__(clock=lambda: 0.0)
        self._null = contextlib.nullcontext()

    def span(self, name, **args):
        return self._null

    def step(self, name, step, **args):
        return self._null

    def instant(self, name, **args):
        pass

    def save(self, path):
        return None


NULL_TRACER = NullTracer()


@contextlib.contextmanager
def device_trace(logdir: Optional[str]):
    """Optional ``jax.profiler`` bracket: profiles DEVICE-side execution
    into ``logdir`` (TensorBoard/XPlane format), with the spans of every
    ``SpanTracer`` that runs inside it on the host's line of the same
    profile.  No-op when ``logdir`` is falsy.  A profiler that cannot
    start or stop raises: a run asked for a device trace never finishes
    without one."""
    if not logdir:
        yield
        return
    import jax
    jax.profiler.start_trace(logdir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def validate_chrome_trace(doc: dict, *, required_names=()) -> dict:
    """Structural validation used by tests and the CI artifact check:
    ``traceEvents`` envelope, complete events carry ts+dur, timestamps
    monotone after the declared sort, required span names present.
    Returns {"events": n, "names": set} on success, raises otherwise."""
    assert isinstance(doc, dict) and "traceEvents" in doc, \
        "not a chrome-trace envelope"
    evs = [e for e in doc["traceEvents"] if e.get("ph") != "M"]
    assert evs, "trace has no events"
    names = set()
    last_ts = None
    for e in evs:
        assert {"name", "ph", "ts", "pid", "tid"} <= set(e), e
        if e["ph"] == "X":
            assert "dur" in e and e["dur"] >= 0, e
        assert last_ts is None or e["ts"] >= last_ts, \
            f"non-monotone ts: {e}"
        last_ts = e["ts"]
        names.add(e["name"])
    missing = set(required_names) - names
    assert not missing, f"required span names missing from trace: {missing}"
    return {"events": len(evs), "names": names}
