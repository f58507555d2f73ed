"""The open-loop client: submits each request when it is due and drives
the engine in between, on the wall clock.

A request is submitted at its due time, or at once when a step overran
it; every output token is stamped when the engine hands it over.  The
traffic's lead-in runs before the window opens, so the window measures
the engine under the mix's load and not its ramp from empty.  In a traced run the client marks its own
calls (``bench.submit``, ``bench.step`` for one pass of the front end,
``bench.wait``) as profiler annotations, and wrappers around the
engine's scheduling pass (``bench.schedule``) and jitted step mark the
call (``bench.forward``) and the wait for its result (``bench.sync``)
and keep each step's inputs and counters.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time

import numpy as np


@dataclasses.dataclass
class Record:
    due: float
    prompt: object          # (P,) int32 token ids
    submit: float = float("nan")
    tokens: list = dataclasses.field(default_factory=list)   # times
    out: list = dataclasses.field(default_factory=list)      # token ids
    done: bool = False
    failed: str = ""
    handle: object = None


@dataclasses.dataclass
class Step:
    start: float
    rows: int
    pos: object             # device array of positions
    aux: dict               # device scalars: the step's plan statistics
    emitted: int = 0


def annotate(enabled: bool):
    if not enabled:
        return lambda name: contextlib.nullcontext()
    import jax
    return jax.profiler.TraceAnnotation


class StepTap:
    """Wraps the engine's jitted step to keep what each call was given
    and returned (no host transfer: device arrays are read after the
    window), and marks the engine's scheduling pass."""

    def __init__(self, engine, clock, ann):
        self.fn, self.clock, self.ann = engine._pstep, clock, ann
        self.steps: list[Step] = []
        engine._pstep = self
        schedule = engine.schedule

        def annotated(pending):
            with ann("bench.schedule"):
                return schedule(pending)
        engine.schedule = annotated

    def __call__(self, params, pools, batch, pos, *rest):
        with self.ann("bench.forward"):
            out = self.fn(params, pools, batch, pos, *rest)
        with self.ann("bench.sync"):
            out[0].block_until_ready()
        self.steps.append(Step(self.clock(), int(pos.shape[0]), pos,
                               out[3]))
        return out


def drive(frontend, reqs, *, t0: float, seconds: float, trace=None,
          tap=None, sample=None):
    """Run the lead-in and the window.  ``reqs`` are
    ``traffic.Request``\\ s in due order, due relative to ``t0``, the
    window's opening (lead-in requests are due before it); the client
    stops at the close, ``t0 + seconds``.  ``trace``: an object whose
    ``start``, ``open`` and ``close`` run two seconds before the window
    opens, when it opens and when it closes.  ``sample(now)``, if given,
    runs once a second.  Returns (records, t_close)."""
    ann = annotate(trace is not None)
    clock = time.perf_counter
    recs = [Record(r.due, r.prompt) for r in reqs]
    by_rid = {}

    def on_token(req, tok):
        rec = by_rid[req.rid]
        rec.tokens.append(clock())
        rec.out.append(int(tok))

    t_close = t0 + seconds
    i, n = 0, len(reqs)
    phase = 0                   # 0 lead-in, 1 profiler on, 2 window open
    t_sample = clock()
    while True:
        now = clock()
        if trace is not None and phase == 0 and now >= t0 - 2.0:
            trace.start()
            phase = 1
        if trace is not None and phase == 1 and now >= t0:
            trace.open()
            phase = 2
        if now >= t_close:
            break
        if sample is not None and now >= t_sample:
            sample(now)
            t_sample += 1.0
        while i < n and t0 + reqs[i].due <= now:
            with ann("bench.submit"):
                rec = recs[i]
                rec.submit = clock()
                try:
                    h = frontend.submit(reqs[i].prompt,
                                        max_new=reqs[i].max_new,
                                        rid=i, on_token=on_token)
                    rec.handle = h
                    by_rid[i] = rec
                except Exception as e:     # refused: counts as failed
                    rec.failed = f"{type(e).__name__}: {e}"
            i += 1
            now = clock()
        if frontend.outstanding:
            before = sum(len(r.tokens) for r in by_rid.values()) \
                if tap is not None else 0
            with ann("bench.step"):
                frontend.poll()
            if tap is not None and tap.steps:
                tap.steps[-1].emitted = sum(
                    len(r.tokens) for r in by_rid.values()) - before
            for rec in by_rid.values():
                if not rec.done and rec.handle.done:
                    rec.done = True
        else:
            nxt = t0 + reqs[i].due if i < n else t_close
            with ann("bench.wait"):
                time.sleep(max(0.0, min(1e-3, nxt - clock())))
    if trace is not None:
        if phase == 0:
            trace.start()
        if phase < 2:
            trace.open()
        trace.close()
    return recs, t_close


def latencies(recs, t0: float, t_close: float) -> dict:
    """End-to-end figures of the window, in milliseconds: the requests
    due in it (``n_due``); per request that finished in the window,
    whenever it was due, its time per output token after the first
    (``tpot``); every gap between consecutive tokens of any request that
    ends in the window (``itl``)."""
    n_due = sum(1 for r in recs if 0.0 <= r.due < t_close - t0)
    tpot = [(r.tokens[-1] - r.tokens[0]) * 1e3 / (len(r.tokens) - 1)
            for r in recs if r.done and len(r.tokens) > 1
            and t0 <= r.tokens[-1] <= t_close]
    itl = [float(b - a) * 1e3 for r in recs
           for a, b in zip(r.tokens, r.tokens[1:]) if t0 <= b <= t_close]
    return {"tpot": tpot, "itl": itl, "n_due": n_due}


def lag_ms(recs, t0: float) -> list:
    """How late the client submitted each request: submit minus due."""
    return [(r.submit - t0 - r.due) * 1e3 for r in recs
            if not np.isnan(r.submit)]


def percentile(values, q: float) -> float:
    """Nearest-rank percentile (q in [0, 100]); NaN for no values."""
    if not values:
        return float("nan")
    s = sorted(values)
    rank = max(1, min(len(s), int(np.ceil(q / 100.0 * len(s)))))
    return float(s[rank - 1])


def tokens_in(recs, start: float, end: float) -> int:
    """Output tokens handed over in [start, end]."""
    return sum(1 for r in recs for t in r.tokens if start <= t <= end)
