"""Open-stream serving front-end: request queue + token streaming.

``ServeEngine.run()`` is a CLOSED batch API — hand it every request up
front, get the finished batch back.  Production traffic is an open
stream: requests arrive while others decode, and callers want tokens as
they are produced, not at retirement.  This module is that front end
(DESIGN.md §11), deliberately thin over the engine:

* **submit()** stamps the request's queue-wait origin (the engine's
  ``lat/queue_wait_s`` measures from here) and registers an optional
  per-request streaming callback.  Nothing runs — admission happens
  inside the next ``poll()``, under whatever admission policy the engine
  was built with (the ``slo`` policy preempts through the same pass).
* **poll()** drives one (or more) scheduling pass + engine step and
  returns the requests that finished during it; each pass is a
  ``serve/poll`` span of the engine's tracer.  Token callbacks fire
  from the engine's ``on_token`` hook as soon as the step's ONE host
  sync has retired its tokens into ``Request.out``.  Streaming therefore
  adds ZERO device syncs, and the streamed sequence is bitwise-identical
  to what a closed-batch ``run()`` would produce (asserted in
  tests/test_serve.py across dense/MoE x paged/contiguous).
* **drain()** polls until the queue is empty or a step budget runs out,
  finalizing censored ``lat/*`` stats on anything still unfinished —
  the open-stream analogue of ``run()``'s drop handling.

One frontend owns one engine: constructing it installs the engine's
``on_token`` hook.
"""
from __future__ import annotations

import itertools
from typing import Callable, Dict, List, Optional

import numpy as np

from repro.serve.engine import Request, ServeEngine

TokenCallback = Callable[[Request, int], None]


class ServingFrontend:
    def __init__(self, engine: ServeEngine):
        self.engine = engine
        self.pending: List[Request] = []
        self._inflight: Dict[int, Request] = {}     # rid -> submitted req
        self._callbacks: Dict[int, TokenCallback] = {}
        self._rids = itertools.count()
        engine.on_token = self._on_token

    # -- submission ----------------------------------------------------
    def submit(self, prompt, *, max_new: int = 16, eos: Optional[int] = None,
               rid: Optional[int] = None,
               slo_ttft: Optional[float] = None,
               slo_tpot: Optional[float] = None,
               seed: Optional[int] = None,
               on_token: Optional[TokenCallback] = None) -> Request:
        """Enter one request into the open queue; returns the Request as
        the caller's handle (poll ``.done`` / ``.out``, or stream via
        ``on_token(req, tok)``).  The queue-wait clock starts HERE.
        ``seed`` keys this request's stochastic sampling stream
        (repro.sampling); None derives one from the engine base + rid."""
        if rid is None:
            rid = next(self._rids)
            while rid in self._inflight:
                rid = next(self._rids)
        elif rid in self._inflight:
            raise ValueError(f"rid {rid} is already in flight")
        req = Request(rid=rid, prompt=np.asarray(prompt, np.int32),
                      max_new=max_new, eos=eos,
                      slo_ttft=slo_ttft, slo_tpot=slo_tpot, seed=seed)
        self.engine.enqueue([req])     # stamps lat/queue_wait_s origin
        self.pending.append(req)
        self._inflight[rid] = req
        if on_token is not None:
            self._callbacks[rid] = on_token
        return req

    def _on_token(self, req: Request, tok: int) -> None:
        cb = self._callbacks.get(req.rid)
        if cb is not None:
            cb(req, tok)

    # -- introspection -------------------------------------------------
    @property
    def outstanding(self) -> int:
        """Requests submitted but not yet finished (queued + active +
        preempted-awaiting-resume)."""
        return sum(1 for r in self._inflight.values() if not r.done)

    # -- driving -------------------------------------------------------
    def poll(self, steps: int = 1) -> List[Request]:
        """Advance the engine by up to ``steps`` scheduling passes +
        engine steps; fire streaming callbacks; return the requests that
        COMPLETED during this poll (retired handles leave the in-flight
        table, so each completion is reported exactly once)."""
        done: List[Request] = []
        tracer = self.engine.obs.tracer
        for _ in range(max(1, steps)):
            with tracer.span("serve/poll"):
                self.engine.schedule(self.pending)
                n = self.engine.step()
                for rid in [rid for rid, r in self._inflight.items()
                            if r.done]:
                    done.append(self._inflight.pop(rid))
                    self._callbacks.pop(rid, None)
            if n == 0 and not self.pending:
                break                  # idle: nothing left to schedule
        return done

    def drain(self, max_steps: int = 512) -> List[Request]:
        """Poll until every submitted request finished or the step budget
        runs out.  Unfinished requests get finite censored ``lat/*``
        stats (engine.finalize_drops) and stay resumable via a later
        poll/drain."""
        done: List[Request] = []
        for _ in range(max_steps):
            done.extend(self.poll())
            if not self.outstanding:
                break
        leftovers = [r for r in self._inflight.values() if not r.done]
        if leftovers:
            self.engine.finalize_drops(leftovers)
        return done
