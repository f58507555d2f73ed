"""Batched continuous-batching engine over a PAGED KV cache.

All active slots decode in ONE jitted forward — this is where the paper's
throughput story meets serving: every MoE layer sees the whole decode
batch and builds exactly one ``DispatchPlan`` per step.  On top of the
PR 3 batched step, the cache is now *paged* (DESIGN.md §9, vLLM-style,
scaled to this container):

* **Paged pool, host block tables.**  The device holds a global pool of
  fixed-size KV blocks (serve/kv_cache.py); each slot owns a block table.
  Reads gather the slot's logical view through the table, writes scatter
  block-granular — the contiguous ``(slots, capacity)`` buffer and its
  device row swaps are gone (slot compaction is a host-side table move).
* **Chunked prefill rides the decode plan.**  Admission assigns a slot
  and nothing else; the prompt is processed as fixed-size chunks of
  tokens that join the decode step's token batch — one forward, one
  DispatchPlan per MoE layer covering decode tokens AND chunk tokens
  together.  Prefill never stalls decoding slots, and MoE plans see
  larger, better-balanced batches (asserted via plan_dispatch counting).
* **Prefix caching.**  Full prompt blocks are content-hashed (chained) at
  admission; hit blocks are refcount-shared, their tokens skip both
  attention prefill and MoE dispatch entirely (chunking starts after the
  cached prefix).  Retired blocks park in an LRU pool for future hits.
* **One sync per step** (unchanged): argmax + EOS compare on device, one
  host transfer for the whole token batch.

Families whose caches are not positional KV (rwkv/ssm recurrent state,
the vlm image-KV cross blocks, zamba2's mamba layers) fall back to the
pre-paging contiguous engine — same public behavior, selected
automatically (or force with ``kv_block_size=0``).

**Observability (DESIGN.md §10).**  The engine accepts an
``Observability`` bundle (repro.obs; default: null sinks): every piece
of per-step host work is a span, nested as the calls nest — the
scheduling pass (admission, the prefix-hash probe, preemption), and
inside the step span the assembly and its input transfers, the forward
dispatch, the one host sync, postprocessing with token streaming,
prefix registration and retirement, and the step's bookkeeping.  While
a profiler trace runs the spans land in it too.  Recompile events fire
from inside the jitted bodies at trace time, per-step KV-pool occupancy
lands as gauges, the ``StragglerMonitor`` flags slow steps, and
retirement absorbs the request's ``sched/*`` plan stats into
histograms.  All of it is host-side wall-clock over already-materialized
values — NO device op is added, so greedy tokens are bitwise-identical
with observability on or off (asserted in tests/test_obs.py).
Per-request latency accounting (``lat/*`` in ``Request.stats``: queue
wait, TTFT, TPOT, E2E — the MoE-Inference-Bench axes) is always on; it
costs a handful of host clock reads per step.  The ``lat/*`` +
``serve/*`` key schema is identical between the paged and contiguous
engines.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models.lm import RunConfig, init_cache, swap_cache_slots
from repro.obs import NOOP, RequestTimeline
from repro.sampling import SamplingConfig
from repro.serve.admission import get_admission
from repro.serve.kv_cache import PagedKVCache, paged_supported
from repro.serve.step import (make_paged_step, make_slot_decode_step,
                              make_slot_prefill_step)

DEFAULT_KV_BLOCK = 16


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray              # (P,) int32
    max_new: int = 16
    eos: Optional[int] = None
    out: List[int] = dataclasses.field(default_factory=list)
    done: bool = False
    # per-request SLO deadlines (seconds on the engine clock; None = no
    # deadline).  The ``slo`` admission policy admits by TTFT-deadline
    # feasibility and preempts active requests that blew them; everything
    # else ignores these fields.
    slo_ttft: Optional[float] = None
    slo_tpot: Optional[float] = None
    # per-request sampling seed (repro.sampling); None derives a unique
    # seed from the engine's SamplingConfig base + rid.  Greedy ignores it.
    seed: Optional[int] = None
    # dispatch-plan telemetry, set at retirement from the request's final
    # step (router aux + sched/* ScheduleStats when the model is MoE and
    # stats are enabled), summed over the MoE layers of that step; the
    # plan is shared by every token in that step, and ``serve/decode_batch``
    # records how many slots decoded in it.  Paged runs add
    # ``serve/prefix_hit_tokens`` (prompt tokens served from shared
    # blocks, never dispatched) and ``serve/prefill_forwards`` (chunk
    # steps this request's prompt rode in).
    stats: dict = dataclasses.field(default_factory=dict)


class ServeEngine:
    def __init__(self, cfg: ModelConfig, params, *, slots: int = 4,
                 capacity: int = 256, rc: Optional[RunConfig] = None,
                 admission: str = "fcfs",
                 kv_block_size: Optional[int] = None,
                 prefix_cache: bool = True, prefill_chunk: int = 32,
                 obs=None, sampling: Optional[SamplingConfig] = None):
        self.cfg = cfg
        # sampling config (repro.sampling); the greedy default keeps the
        # literal argmax path inside the jitted steps, bitwise-identical
        # to every prior PR
        self.sampling = sampling or SamplingConfig()
        # observability bundle (repro.obs); the null default makes every
        # span/counter call a no-op — zero cost when off
        self.obs = obs or NOOP
        self._clock = self.obs.clock
        # serving default: the dynamic schedule policy — production traffic
        # is skewed and decode batches are small, exactly the regime where
        # the fixed tile layout pads worst (DESIGN.md §3) — with per-plan
        # telemetry on so operators see padding/drop behavior per request
        self.rc = rc or RunConfig(q_chunk=64, kv_chunk=64,
                                  schedule_policy="dynamic", moe_stats=True)
        if self.rc.quant != "none" and cfg.is_moe:
            # load-time transform: routed experts compressed under the
            # selected scheme (idempotent if params already carry the tag)
            from repro.quantization import quantize_params_tree
            params = quantize_params_tree(params, self.rc.quant)
        self.params = params
        self.slots = slots
        self.capacity = capacity
        if kv_block_size is None:       # auto: paged wherever pageable
            kv_block_size = DEFAULT_KV_BLOCK if paged_supported(cfg) else 0
        self.kv_block_size = kv_block_size
        self.paged = kv_block_size > 0
        self.prefill_chunk = max(1, prefill_chunk)
        self.pos = np.zeros(slots, np.int64)          # per-slot positions
        # active requests occupy slots [0, n_active) — prefix invariant
        # (paged keeps it too: compaction is a host-side table move)
        self.active: List[Optional[Request]] = [None] * slots
        self.n_active = 0
        # per-active-request shared step aux (device scalars; materialized
        # into Request.stats at retirement), keyed by rid — id(req) of a
        # retired request can be recycled by the allocator
        self._last_aux: Dict[int, dict] = {}
        # per-request latency timelines (host wall-clock; always on) —
        # keyed by rid, created at admission, popped at retirement; submit
        # stamps recorded by run() for queue-wait accounting
        self._timing: Dict[int, RequestTimeline] = {}
        self._submit: Dict[int, float] = {}
        self._step_idx = 0
        # requests still in flight/pending when run()'s step budget ran out
        self.dropped: List[Request] = []
        self._admission_name = admission
        self._admission = get_admission(admission)
        # streaming hook: called as ``on_token(req, tok)`` for each token
        # the step's ONE host sync retired into ``req.out``, once the
        # step's tokens are in (``serve/stream``) — the front-end fans it
        # out to per-request callbacks.  Purely host-side
        # (fires on already-materialized ints), so streaming adds no
        # device syncs and tokens are bitwise-identical to batch run().
        self.on_token = None
        # preempted requests' engine-side cursors, keyed by rid (the KV
        # table itself parks inside PagedKVCache under the same key)
        self._parked: Dict[int, dict] = {}
        # per-slot prefill source: the prompt for fresh admissions, or
        # prompt + out[:-1] when a resume must replay (re-prefill) a
        # preempted request whose parked KV is gone (contiguous mode, or
        # a reclaimed paged park)
        self._seq: List[Optional[np.ndarray]] = [None] * slots
        # preemption/resume accounting (plain ints: artifact counters must
        # not depend on an obs sink being attached)
        self.n_preempted = 0
        self.n_resumed = 0
        # step-cost estimate for SLO feasibility: measured EWMA of wall
        # seconds per step; virtual-time harnesses (the load generator)
        # override via step_time_hint
        self.step_time_hint: Optional[float] = None
        self._ewma_step_s: Optional[float] = None
        # target-model forwards executed (one per step that ran a forward;
        # the speculative engine's benchmark compares this against the
        # non-speculative baseline for its forward-count win)
        self.n_forwards = 0

        if self.paged:
            self.kv = PagedKVCache(cfg, slots, capacity, kv_block_size,
                                   prefix_cache=prefix_cache)
            self.kv.bind_obs(self.obs.metrics, self.obs.tracer)
            self.cache = None
            self._pstep = make_paged_step(cfg, self.rc, self.obs,
                                          self.sampling)
            # prompt-prefill cursor: prompt tokens whose KV is written
            self._prefill_next = np.zeros(slots, np.int64)
            self._prefix_hit = np.zeros(slots, np.int64)
            self._prefill_forwards = np.zeros(slots, np.int64)
        else:
            # ONE batched contiguous cache; slot s owns row s of every leaf
            self.kv = None
            self.cache = init_cache(cfg, slots, capacity)
            self._prefill = make_slot_prefill_step(cfg, self.rc, self.obs,
                                                   self.sampling)
            # one compiled decode step per distinct active count (<= slots)
            self._decode_steps: Dict[int, object] = {}
            self._swap = jax.jit(swap_cache_slots)

        if self.obs.metered and self.rc.quant != "none" and cfg.is_moe:
            # the decode-dominant cost this serving config moves per
            # expert gather: compressed payload bytes under the scheme
            from repro.quantization import QuantTensor
            leaves = jax.tree.leaves(
                self.params, is_leaf=lambda x: isinstance(x, QuantTensor))
            self.obs.metrics.set_gauge(
                "serve/quant_expert_bytes",
                sum(l.nbytes for l in leaves if isinstance(l, QuantTensor)),
                scheme=self.rc.quant)

    # ------------------------------------------------------------------
    def _batch(self, toks):
        b = {"tokens": toks}
        if self.cfg.cross_attn_every:
            b["image_embeds"] = jnp.zeros(
                (toks.shape[0], self.cfg.n_image_tokens, self.cfg.d_model),
                jnp.float32)
        return b

    def _req_seed(self, req: Request) -> int:
        """The request's effective sampling seed: its own, or a unique
        per-rid derivation from the engine base — so requests in one
        batch draw independent streams by default (tests/test_sampling.py
        asserts independence and batched-vs-unbatched identity)."""
        return req.seed if req.seed is not None \
            else self.sampling.seed + req.rid

    def admit(self, req: Request) -> bool:
        """Claim a free slot for ``req``; False if full.

        Contiguous mode prefills the whole prompt here (one forward).
        Paged mode only attaches prefix-cache hits and sets the chunk
        cursor — the prompt is processed chunk-by-chunk inside subsequent
        ``step()`` token batches, so admission never runs a forward."""
        if self.n_active >= self.slots:
            return False
        if any(r is not None and r.rid == req.rid for r in self.active):
            # telemetry is keyed by rid; two live requests sharing one
            # would silently cross their stats and crash at retirement
            raise ValueError(f"rid {req.rid} is already active")
        t_admit = self._clock()
        with self.obs.tracer.span("serve/admit", rid=req.rid,
                                  prompt_tokens=len(req.prompt)):
            self._admit(req, t_admit)
        self.obs.metrics.inc("serve/admitted")
        return True

    def _emit(self, req: Request, tok: int, t: float,
              emitted: list) -> None:
        """Retire one token into ``req.out`` (post-host-sync): latency
        stamp, zero additional device work; ``emitted`` collects it for
        the step's ``_stream``."""
        req.out.append(tok)
        self._timing[req.rid].on_token(t)
        emitted.append((req, tok))

    def _stream(self, emitted: list) -> None:
        """Hand the tokens a step produced to the streaming hook, in the
        order they were emitted."""
        if self.on_token is None or not emitted:
            return
        with self.obs.tracer.span("serve/stream", tokens=len(emitted)):
            for req, tok in emitted:
                self.on_token(req, tok)

    def _admit(self, req: Request, t_admit: float) -> None:
        s = self.n_active
        # a resumed (previously preempted/dropped) request keeps its
        # original timeline so TTFT/queue-wait/E2E stay anchored at the
        # first submission; a fresh request gets a new one.  Queue wait
        # spans run()'s submit stamp -> slot claim; a request admitted
        # directly (no run()) has zero queue wait by definition.
        tl = self._timing.get(req.rid)
        resumed = tl is not None       # preempted earlier: timeline kept
        if tl is None:
            tl = RequestTimeline(submit=self._submit.pop(req.rid, t_admit),
                                 admit=t_admit)
            self._timing[req.rid] = tl
        # prefill source: fresh prompts verbatim; a resume with no parked
        # KV replays prompt + generated-so-far (minus the last token,
        # which seeds the next decode) — greedy determinism makes the
        # recomputed KV identical to what preemption threw away
        if req.out:
            seq = np.concatenate([np.asarray(req.prompt, np.int64),
                                  np.asarray(req.out[:-1], np.int64)]
                                 ).astype(np.int32)
        else:
            seq = req.prompt
        if self.paged:
            park = self._parked.pop(req.rid, None)
            if park is not None and self.kv.resume_slot(s, req.rid):
                # host-side table un-park: KV intact, nothing recomputed
                self.pos[s] = park["pos"]
                self._prefill_next[s] = park["prefill_next"]
                self._prefix_hit[s] = park["prefix_hit"]
                self._prefill_forwards[s] = park["prefill_forwards"]
                self._seq[s] = park["seq"]
            else:
                # capacity governs, not the block-rounded table size: a
                # prompt in the rounding slack would fit the blocks but
                # diverge from the contiguous engine's (slots, capacity)
                # rows
                limit = min(self.capacity,
                            self.kv.blocks_per_slot * self.kv.block_size)
                if len(seq) > limit:
                    # fail loudly BEFORE claiming a slot (a mid-step
                    # failure would take every active request's state
                    # down with it)
                    raise ValueError(
                        f"prompt of {len(seq)} tokens exceeds slot "
                        f"capacity {limit} ({self.kv.blocks_per_slot} "
                        f"blocks of {self.kv.block_size})")
                n_cached = self.kv.attach_prefix(s, seq)
                self.pos[s] = n_cached
                self._prefill_next[s] = n_cached
                self._prefix_hit[s] = n_cached
                self._prefill_forwards[s] = 0
                self._seq[s] = seq
            self._last_aux[req.rid] = {}
        else:
            toks = jnp.asarray(seq, jnp.int32)[None]
            with self.obs.tracer.span("serve/prefill", rid=req.rid,
                                      prompt_tokens=len(seq)):
                tok, self.cache, aux = self._prefill(
                    self.params, self.cache, self._batch(toks),
                    jnp.int32(s), jnp.int32(self._req_seed(req)))
                self.n_forwards += 1
                self.pos[s] = len(seq)
                first = int(tok[0])             # forces the prefill sync
            self._last_aux[req.rid] = aux
            self._seq[s] = seq
        self.active[s] = req
        self.n_active += 1
        if not self.paged and not resumed:
            # first token: TTFT stamp + stream.  A resume's prefill output
            # is a token the request already streamed (the replay's last
            # logits re-predict out[-1]) — recompute only, never re-emit.
            emitted = []
            self._emit(req, first, self._clock(), emitted)
            self._stream(emitted)
        if resumed:
            self.n_resumed += 1
            self.obs.metrics.inc("serve/resumed")
            self.obs.tracer.instant("serve/resume", rid=req.rid, slot=s)

    # ------------------------------------------------------------------
    def step(self) -> int:
        """One engine step: ONE jit call, ONE host sync, covering every
        active slot.  Returns the number of TOKENS processed (== active
        slots in a pure-decode step; larger while prompts are chunk-
        prefilling in paged mode; 0 when idle)."""
        t0 = self._clock()
        n = self._step_paged() if self.paged else self._step_contig()
        if n:
            dt = self._clock() - t0
            self._ewma_step_s = dt if self._ewma_step_s is None \
                else 0.7 * self._ewma_step_s + 0.3 * dt
        return n

    def step_time_estimate(self) -> float:
        """Expected wall seconds per engine step — what the ``slo``
        admission policy prices TTFT/TPOT feasibility with.  Virtual-time
        harnesses set ``step_time_hint``; otherwise the measured EWMA
        (0.0 until the first step has been timed)."""
        if self.step_time_hint is not None:
            return self.step_time_hint
        return self._ewma_step_s or 0.0

    # -- paged ---------------------------------------------------------
    def _step_paged(self) -> int:
        n = self.n_active
        if n == 0:
            return 0
        obs, i_step = self.obs, self._step_idx
        obs.step_begin(i_step)
        with obs.tracer.step("serve/step", i_step, active=n):
            # assemble the step's token batch: per slot either its decode
            # token or the next chunk of its prompt
            with obs.tracer.span("serve/assemble"):
                rows = []                   # (slot, token, position, kind)
                n_decode = n_final = 0
                for s in range(n):
                    r = self.active[s]
                    seq = self._seq[s]
                    nx = int(self._prefill_next[s])
                    P = len(seq)
                    if nx < P:
                        c = min(self.prefill_chunk, P - nx)
                        for j in range(c):
                            # the last prefill token seeds the request's
                            # first output ("final") — except on a resume
                            # replay, whose outputs already exist: the
                            # replay only rebuilds KV, it emits nothing
                            kind = ("final" if nx + j == P - 1
                                    and not r.out else "chunk")
                            n_final += kind == "final"
                            rows.append((s, int(seq[nx + j]),
                                         nx + j, kind))
                    else:
                        n_decode += 1
                        rows.append((s, r.out[-1], int(self.pos[s]),
                                     "decode"))
                for s in {row[0] for row in rows}:
                    self.kv.ensure_allocated(
                        s, max(p for sl, _, p, _ in rows if sl == s))
                with obs.tracer.span("serve/inputs", tokens=len(rows)):
                    tables = jnp.asarray(
                        self.kv.table_rows([row[0] for row in rows]))
                    toks = jnp.asarray([[t] for _, t, _, _ in rows],
                                       jnp.int32)
                    pos = jnp.asarray([p for _, _, p, _ in rows], jnp.int32)
                    eos = jnp.asarray(
                        [(-1 if (k != "decode"
                                 or self.active[s].eos is None)
                          else self.active[s].eos)
                         for s, _, _, k in rows], jnp.int32)
                    # stochastic-draw keys: each row's (request seed,
                    # output index it produces).  Chunk rows' draws are
                    # discarded; keyed draws are stateless, so they
                    # disturb nothing.
                    seeds = jnp.asarray(
                        [self._req_seed(self.active[s])
                         for s, _, _, _ in rows], jnp.int32)
                    counters = jnp.asarray(
                        [(len(self.active[s].out) if k == "decode" else 0)
                         for s, _, _, k in rows], jnp.int32)
            with obs.tracer.span("serve/forward", tokens=len(rows),
                                 decode_rows=n_decode,
                                 prefill_rows=len(rows) - n_decode,
                                 emits=n_decode + n_final):
                tok, eos_hit, self.kv.pools, aux = self._pstep(
                    self.params, self.kv.pools, self._batch(toks), pos,
                    tables, eos, seeds, counters)
                self.n_forwards += 1
            with obs.tracer.span("serve/host_sync"):   # the ONE host sync
                tok_np, eos_np = jax.device_get((tok, eos_hit))
            # one stamp shared by every token this step produced (they
            # all come from the same forward)
            t_now = self._clock()

            with obs.tracer.span("serve/postprocess"):
                decode_row: Dict[int, int] = {}
                chunks = np.zeros(n, np.int64)
                emitted = []
                for i, (s, _t, _p, kind) in enumerate(rows):
                    self._last_aux[self.active[s].rid] = aux
                    if kind == "decode":
                        self._emit(self.active[s], int(tok_np[i]), t_now,
                                   emitted)
                        self.pos[s] += 1
                        decode_row[s] = i
                    else:
                        chunks[s] += 1
                        if kind == "final":   # prompt complete: 1st token
                            self._emit(self.active[s], int(tok_np[i]),
                                       t_now, emitted)
                self._stream(emitted)
                for s in np.nonzero(chunks)[0]:
                    self._prefill_next[s] += chunks[s]
                    self.pos[s] += chunks[s]
                    self._prefill_forwards[s] += 1
                    self.kv.register_filled(int(s), self._seq[s],
                                            int(self._prefill_next[s]))
                # retire top-down so compaction (move-last-into-freed)
                # never moves a slot we still have to examine
                for s in range(n - 1, -1, -1):
                    if s not in decode_row:
                        continue
                    r = self.active[s]
                    if bool(eos_np[decode_row[s]]) \
                            or len(r.out) >= r.max_new \
                            or self.pos[s] >= self.capacity - 1:
                        self._retire(s, decode_batch=n_decode)
            self._end_step(i_step, tokens=len(rows))
        return len(rows)

    def _end_step(self, i_step: int, *, tokens: int) -> None:
        """Close the step's observability bracket: straggler window,
        per-step counters, KV-pool occupancy gauges."""
        obs = self.obs
        obs.step_end(i_step)
        self._step_idx += 1
        if obs.metered:
            obs.metrics.inc("serve/steps")
            obs.metrics.inc("serve/step_tokens", tokens)
            if self.paged:
                st = self.kv.stats()
                for k in ("blocks_total", "blocks_in_use",
                          "blocks_parked"):
                    obs.metrics.set_gauge(f"kv/{k}", st[k])

    # -- contiguous (pre-paging fallback) ------------------------------
    def _step_contig(self) -> int:
        n = self.n_active
        if n == 0:
            return 0
        obs, i_step = self.obs, self._step_idx
        obs.step_begin(i_step)
        with obs.tracer.step("serve/step", i_step, active=n):
            with obs.tracer.span("serve/assemble"):
                reqs = self.active[:n]
                fn = self._decode_steps.get(n)
                if fn is None:
                    fn = self._decode_steps[n] = make_slot_decode_step(
                        self.cfg, self.rc, n, self.obs, self.sampling)
                with obs.tracer.span("serve/inputs", tokens=n):
                    last = jnp.asarray([[r.out[-1]] for r in reqs],
                                       jnp.int32)                # (n, 1)
                    pos = jnp.asarray(self.pos[:n], jnp.int32)   # (n,)
                    eos = jnp.asarray([-1 if r.eos is None else r.eos
                                       for r in reqs], jnp.int32)
                    seeds = jnp.asarray([self._req_seed(r) for r in reqs],
                                        jnp.int32)
                    counters = jnp.asarray([len(r.out) for r in reqs],
                                           jnp.int32)
            with obs.tracer.span("serve/forward", tokens=n, decode_rows=n,
                                 prefill_rows=0, emits=n):
                tok, eos_hit, self.cache, aux = fn(
                    self.params, self.cache, self._batch(last), pos, eos,
                    seeds, counters)
                self.n_forwards += 1
            with obs.tracer.span("serve/host_sync"):   # the ONE host sync
                tok_np, eos_np = jax.device_get((tok, eos_hit))
            t_now = self._clock()
            with obs.tracer.span("serve/postprocess"):
                emitted = []
                for s, r in enumerate(reqs):
                    self._emit(r, int(tok_np[s]), t_now, emitted)
                    self.pos[s] += 1
                    self._last_aux[r.rid] = aux
                self._stream(emitted)
                # retire top-down so the swap-with-last compaction never
                # moves a slot we still have to examine
                for s in range(n - 1, -1, -1):
                    r = self.active[s]
                    if bool(eos_np[s]) or len(r.out) >= r.max_new \
                            or self.pos[s] >= self.capacity - 1:
                        self._retire(s, decode_batch=n)
            self._end_step(i_step, tokens=n)
        return n

    # ------------------------------------------------------------------
    def _retire(self, s: int, *, decode_batch: int) -> None:
        """Free slot ``s``: materialize telemetry, keep the active prefix
        contiguous (paged: host-side table move + block refcount release;
        contiguous: device row swap).

        ``Request.stats`` leaves with ONE schema across both engines
        (asserted in tests/test_obs.py): the step plan's aux (``sched/*``
        when MoE stats are on), the ``serve/*`` engine counters —
        ``decode_batch``, ``prefill_forwards`` (contiguous: always 1.0,
        the whole-prompt admission prefill), ``prefix_hit_tokens``
        (contiguous: always 0.0, no prefix index) — and the ``lat/*``
        latency family (obs/latency.py)."""
        req = self.active[s]
        with self.obs.tracer.span("serve/retire", rid=req.rid):
            req.stats = {k: float(v)
                         for k, v in self._last_aux.pop(req.rid).items()}
            req.stats["serve/decode_batch"] = float(decode_batch)
            if self.paged:
                req.stats["serve/prefix_hit_tokens"] = \
                    float(self._prefix_hit[s])
                req.stats["serve/prefill_forwards"] = \
                    float(self._prefill_forwards[s])
                self.kv.release_slot(s)
            else:
                req.stats["serve/prefix_hit_tokens"] = 0.0
                req.stats["serve/prefill_forwards"] = 1.0
            self._compact(s)
            tl = self._timing.pop(req.rid, None)
            if tl is not None:
                req.stats.update(tl.finalize(end=self._clock()))
            req.done = True
            obs = self.obs
            if obs.metered:
                m = obs.metrics
                m.inc("serve/completed")
                for key in ("ttft_s", "tpot_s", "queue_wait_s", "e2e_s"):
                    if f"lat/{key}" in req.stats:
                        m.observe(f"serve/{key}", req.stats[f"lat/{key}"])
                # SLO outcome at retirement: deadline misses by family
                lat = req.stats
                if req.slo_ttft is not None \
                        and lat.get("lat/ttft_s", 0.0) > req.slo_ttft:
                    m.inc("serve/slo_ttft_miss")
                if req.slo_tpot is not None \
                        and lat.get("lat/tpot_s", 0.0) > req.slo_tpot:
                    m.inc("serve/slo_tpot_miss")
                # absorb the retirement-time plan stats (summed over the
                # MoE layers of the request's final step) as histogram
                # samples
                m.observe_many("", {k: v for k, v in req.stats.items()
                                    if k.startswith("sched/")})
                # under EP the skew table must stay honest: dropped-
                # token totals from the sharded/replicated dispatch
                # accumulate into a dedicated counter
                if self.rc.ep and "sched/dropped_rows" in req.stats:
                    m.inc("serve/ep_dropped_tokens",
                          int(req.stats["sched/dropped_rows"]))

    def _compact(self, s: int) -> None:
        """Vacate slot ``s`` keeping the active prefix contiguous (paged:
        host-side table move; contiguous: device row swap).  The slot's KV
        must already be released or parked by the caller."""
        last = self.n_active - 1
        if self.paged:
            if s != last:
                self.kv.move_slot(s, last)
                self.active[s] = self.active[last]
                self.pos[s] = self.pos[last]
                self._prefill_next[s] = self._prefill_next[last]
                self._prefix_hit[s] = self._prefix_hit[last]
                self._prefill_forwards[s] = self._prefill_forwards[last]
                self._seq[s] = self._seq[last]
            self._prefill_next[last] = 0
            self._prefix_hit[last] = 0
            self._prefill_forwards[last] = 0
        else:
            if s != last:
                self.cache = self._swap(self.cache, jnp.int32(s),
                                        jnp.int32(last))
                self.active[s] = self.active[last]
                self.pos[s] = self.pos[last]
                self._seq[s] = self._seq[last]
        self._seq[last] = None
        self.active[last] = None
        self.pos[last] = 0
        self.n_active -= 1

    def preempt(self, s: int) -> Request:
        """Evict the request in slot ``s`` mid-flight (the SLO admission
        policy's lever against over-budget/deadline-blown requests).  The
        request keeps ``done=False`` and its partial ``out``; paged mode
        parks its block table host-side under its rid (resume is pure
        bookkeeping — no KV recompute unless allocation pressure reclaims
        the park), contiguous mode abandons the cache row (resume
        re-prefills prompt + generated tokens, token-identical by greedy
        determinism).  A finite censored ``lat/*`` snapshot lands in
        ``Request.stats`` immediately so a never-resumed victim still
        reports real latency numbers."""
        if not (0 <= s < self.n_active):
            raise ValueError(f"no active request in slot {s} "
                             f"(n_active={self.n_active})")
        req = self.active[s]
        t_now = self._clock()
        if self.paged:
            self._parked[req.rid] = {
                "pos": int(self.pos[s]),
                "prefill_next": int(self._prefill_next[s]),
                "prefix_hit": int(self._prefix_hit[s]),
                "prefill_forwards": int(self._prefill_forwards[s]),
                "seq": self._seq[s],
            }
            self.kv.park_slot(s, req.rid)
        self._compact(s)
        self._last_aux.pop(req.rid, None)
        # censored latency snapshot: finite now, overwritten wholesale if
        # the request later resumes and retires.  The timeline itself
        # stays keyed so the resume keeps the original submit anchor.
        tl = self._timing.get(req.rid)
        if tl is not None:
            req.stats = dict(tl.finalize(end=t_now))
            req.stats["serve/preempted"] = 1.0
        self.n_preempted += 1
        self.obs.metrics.inc("serve/preempted")
        self.obs.tracer.instant("serve/preempt", rid=req.rid, slot=s,
                                decode_tokens=len(req.out))
        return req

    def enqueue(self, requests: List[Request]) -> List[Request]:
        """Stamp submit times and return the sublist eligible for
        admission (not done, not already occupying a slot).  Resubmission
        keeps a request's original queue-wait origin."""
        live = {id(r) for r in self.active if r is not None}
        pending = [r for r in requests if not r.done and id(r) not in live]
        t_submit = self._clock()
        for r in pending:
            self._submit.setdefault(r.rid, t_submit)
        return pending

    def schedule(self, pending: List[Request]) -> None:
        """One scheduling pass: let the admission policy preempt (policies
        exposing a ``.preempt(engine, pending)`` hook, e.g. ``slo``), then
        fill free slots from ``pending`` (mutated in place; victims of
        preemption rejoin it, resumable)."""
        with self.obs.tracer.span("serve/schedule", pending=len(pending)):
            pre = getattr(self._admission, "preempt", None)
            if pre is not None and pending:
                for s in sorted(pre(self, pending), reverse=True):
                    pending.append(self.preempt(s))
            while pending and self.n_active < self.slots:
                self.admit(pending.pop(self._admission(pending,
                                                       engine=self)))

    def run(self, requests: List[Request], max_steps: int = 512):
        """Drive admission + decode until done (or the step budget runs
        out).  Returns the completed requests in submission order; requests
        still in flight or never admitted keep ``done=False`` (with any
        partial ``out``) and are collected in ``self.dropped``.  A later
        ``run`` may resume them: requests already occupying a slot (or
        already done) are excluded from admission so they are never
        re-prefilled, but active slots keep decoding."""
        pending = self.enqueue(requests)
        self.dropped = []
        for _ in range(max_steps):
            self.schedule(pending)
            if self.step() == 0 and not pending:
                break
        self.dropped = [r for r in requests if not r.done]
        if self.dropped:
            self.finalize_drops(self.dropped)
            self.obs.metrics.inc("serve/dropped", len(self.dropped))
            self.obs.tracer.instant("serve/step_budget_exhausted",
                                    dropped=len(self.dropped))
        return [r for r in requests if r.done]

    def finalize_drops(self, requests: List[Request]) -> None:
        """Give every unfinished request a FINITE censored ``lat/*``
        snapshot (clocks stopped now) so all-dropped runs still report
        real latency numbers instead of silently vanishing from
        ``latency_summary``.  ``serve/dropped`` marks the censoring; a
        later resume-and-retire overwrites the snapshot wholesale."""
        t_now = self._clock()
        for r in requests:
            if r.done:
                continue
            tl = self._timing.get(r.rid)
            if tl is None:      # never admitted: pure queue wait
                tl = RequestTimeline(
                    submit=self._submit.get(r.rid, t_now), admit=t_now)
            stats = dict(tl.finalize(end=t_now))
            stats["serve/dropped"] = 1.0
            if r.stats.get("serve/preempted"):
                stats["serve/preempted"] = 1.0
            r.stats = stats

    def describe(self, *, seed=None) -> dict:
        """The cell config that makes a results/serve artifact row
        self-describing (report.py renders these columns)."""
        d = {"arch": self.cfg.name, "slots": self.slots,
             "capacity": self.capacity, "admission": self._admission_name,
             "executor": self.rc.executor,
             "schedule_policy": self.rc.schedule_policy,
             "quant": self.rc.quant, "kv_block_size": self.kv_block_size,
             "prefill_chunk": self.prefill_chunk if self.paged else 0,
             "paged_attn": self.rc.paged_attn,
             "autotune": self.rc.autotune,
             "sampling": self.sampling.method,
             "temperature": self.sampling.temperature,
             "sampling_seed": self.sampling.seed}
        if seed is not None:
            d["seed"] = seed
        return d
