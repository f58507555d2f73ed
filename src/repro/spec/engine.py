"""Speculative decoding engine: a draft model proposes, the paged target
verifies k+1 positions per slot in ONE forward (DESIGN.md §13).

``SpecEngine`` layers on :class:`~repro.serve.engine.ServeEngine` and
changes NOTHING about admission, chunked prefill, prefix caching,
preemption, or retirement — it swaps the steady-state decode step for a
speculative round:

1. **Draft.**  A small draft model (its own params + its own
   ``PagedKVCache`` pool over the same slot layout) chains ``k``
   single-token proposal steps per active slot — no host sync between
   them (serve/step.py ``make_spec_draft_step``).
2. **Verify.**  The target scores all ``n_active · (k+1)`` rows — each
   slot's last emitted token plus its k proposals at positions
   ``[pos, pos+k]`` — in ONE batched forward riding the exact
   multi-token-rows-per-slot machinery chunked prefill built (PR 5): one
   DispatchPlan per MoE layer covers the whole verify sweep (asserted in
   tests/test_spec.py).  Accept/rejection math runs on device; the round
   costs ONE host sync total.
3. **Rollback.**  The accepted prefix + bonus token are emitted; both KV
   pools truncate back to the new sequence length via
   ``PagedKVCache.truncate_slot`` — a host-side block-table rollback that
   frees whole rejected blocks to the pool (prefix hashes past the
   truncation point are invalidated there).  No device work.

**Draft-state discipline.**  The draft KV pool is *derived* state — every
byte is recomputable from (draft params, the token sequence).  One
cursor, ``_dnext[s]`` = number of leading positions of slot ``s`` the
draft has processed, tracks it; ``_draft_catch_up()`` replays any gap
``[_dnext, pos)`` through the ordinary paged draft step (argmax
discarded), chunked like prefill.  That single mechanism uniformly
covers draft prompt prefill (mirroring the target's chunked prefill),
post-base-step mirroring, and preempt/resume — preemption simply
RELEASES the draft table (the target's parks; re-deriving the draft's is
a latency cost, never a correctness one).

**Correctness bar** (tests/test_spec.py): with greedy sampling the
emitted stream is token-IDENTICAL to the non-speculative engine for ANY
draft model — each accepted token equals the target argmax at its output
index by the verify construction — fuzzed over k × paged block size ×
draft quality (rejection points).  Stochastic sampling implements
standard rejection sampling against the draft distribution; keyed draws
(repro.sampling) make accepted streams reproducible per seed.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.configs.base import ModelConfig, reduced
from repro.serve.engine import ServeEngine
from repro.serve.kv_cache import PagedKVCache
from repro.serve.step import (make_paged_step, make_spec_draft_step,
                              make_spec_verify_step)


def make_draft_config(target_cfg: ModelConfig, base: str = "smollm-360m",
                      *, reduce: bool = False, layers: int = 2,
                      d_model: int = 128) -> ModelConfig:
    """A draft config vocab-aligned with ``target_cfg`` (rejection
    sampling compares the two distributions token-for-token, so the
    vocabularies must match exactly).  ``reduce=True`` shrinks the draft
    for CPU smoke runs, mirroring how the benchmarks reduce targets."""
    cfg = get_config(base)
    if reduce:
        cfg = reduced(cfg, layers=layers, d_model=d_model,
                      vocab=target_cfg.vocab_size)
    return cfg.replace(vocab_size=target_cfg.vocab_size)


class SpecEngine(ServeEngine):
    """ServeEngine + draft-propose / target-verify / rollback rounds."""

    def __init__(self, cfg: ModelConfig, params, *, draft_cfg: ModelConfig,
                 draft_params, spec_k: int = 4, **kw):
        prefix_cache = kw.get("prefix_cache", True)
        super().__init__(cfg, params, **kw)
        if not self.paged:
            raise ValueError(
                "speculative decoding needs the paged engine (rollback is "
                "a block-table truncation); got a contiguous-cache config "
                "— pass kv_block_size > 0 / a pageable architecture")
        if draft_cfg.vocab_size != cfg.vocab_size:
            raise ValueError(
                f"draft vocab {draft_cfg.vocab_size} != target vocab "
                f"{cfg.vocab_size}; rejection sampling compares the two "
                "distributions per token id (make_draft_config aligns them)")
        if spec_k < 1:
            raise ValueError(f"spec_k must be >= 1, got {spec_k}")
        self.spec_k = spec_k
        self.draft_cfg = draft_cfg
        self.draft_params = draft_params
        # the draft runs the same executor/chunking but never quantizes
        # and never collects MoE plan stats (its aux is discarded)
        self.drc = self.rc._replace(quant="none", moe_stats=False)
        self.dkv = PagedKVCache(draft_cfg, self.slots, self.capacity,
                                self.kv_block_size,
                                prefix_cache=prefix_cache)
        self.dkv.bind_obs(self.obs.metrics, self.obs.tracer)
        # catch-up reuses the ordinary paged step (tokens in, argmax out —
        # discarded); proposals/verification use the dedicated spec steps
        self._dstep = make_paged_step(draft_cfg, self.drc, self.obs,
                                      self.sampling)
        self._draft_step = make_spec_draft_step(draft_cfg, self.drc,
                                                self.sampling, self.obs)
        self._verify_step = make_spec_verify_step(cfg, self.rc,
                                                  self.sampling, spec_k,
                                                  self.obs)
        # draft progress cursor: leading positions of slot s whose tokens
        # the draft has processed (KV written)
        self._dnext = np.zeros(self.slots, np.int64)
        # speculation accounting (plain ints: artifact counters must not
        # depend on an obs sink being attached)
        self.n_spec_rounds = 0
        self.n_drafted = 0
        self.n_accepted = 0
        self.n_draft_forwards = 0

    # ------------------------------------------------------------------
    @property
    def acceptance_rate(self) -> float:
        """Accepted draft tokens / drafted tokens (1.0 until the first
        round so an all-baseline run reports a neutral value)."""
        return self.n_accepted / self.n_drafted if self.n_drafted else 1.0

    def describe(self, *, seed=None) -> dict:
        d = super().describe(seed=seed)
        d["spec_k"] = self.spec_k
        d["spec_draft"] = self.draft_cfg.name
        return d

    # -- slot lifecycle hooks ------------------------------------------
    def _admit(self, req, t_admit) -> None:
        super()._admit(req, t_admit)
        s = self.n_active - 1
        # draft prefix-cache probe mirrors the target's; on a cold cache
        # this is 0 and catch-up prefills the draft chunk-by-chunk
        self._dnext[s] = self.dkv.attach_prefix(s, self._seq[s])

    def _retire(self, s: int, *, decode_batch: int) -> None:
        self.dkv.release_slot(s)
        super()._retire(s, decode_batch=decode_batch)

    def preempt(self, s: int):
        # draft KV is derived state: release rather than park (resume
        # re-derives via catch-up — latency, never correctness)
        self.dkv.release_slot(s)
        return super().preempt(s)

    def _compact(self, s: int) -> None:
        last = self.n_active - 1
        if s != last:
            self.dkv.move_slot(s, last)
            self._dnext[s] = self._dnext[last]
        self._dnext[last] = 0
        super()._compact(s)

    # -- draft bookkeeping ---------------------------------------------
    def _full_tokens(self, s: int) -> np.ndarray:
        """Tokens at positions ``[0, pos[s]]`` of slot ``s``: the prefill
        source then the out-suffix extending it (the engine invariant
        ``pos = len(seq) + len(out) - 1`` once prefill completes)."""
        seq = np.asarray(self._seq[s], np.int64)
        t = int(self.pos[s]) + 1 - len(seq)
        if t <= 0:
            return seq[:int(self.pos[s]) + 1]
        out = np.asarray(self.active[s].out[-t:], np.int64)
        return np.concatenate([seq, out])

    def _draft_catch_up(self) -> None:
        """Feed the draft every token position the target is ahead by
        (``[_dnext, pos)`` per slot), chunked like prefill.  Uniformly
        handles draft prompt prefill, post-base-step mirroring, and
        resume replay; a no-op when every slot is caught up."""
        while True:
            rows = []                              # (slot, token, position)
            for s in range(self.n_active):
                dn, p = int(self._dnext[s]), int(self.pos[s])
                if dn >= p:
                    continue
                full = self._full_tokens(s)
                for j in range(min(self.prefill_chunk, p - dn)):
                    rows.append((s, int(full[dn + j]), dn + j))
            if not rows:
                return
            with self.obs.tracer.span("serve/spec_catch_up",
                                      tokens=len(rows)):
                for s in {r[0] for r in rows}:
                    self.dkv.ensure_allocated(
                        s, max(p for sl, _, p in rows if sl == s))
                tables = jnp.asarray(
                    self.dkv.table_rows([r[0] for r in rows]))
                toks = jnp.asarray([[t] for _, t, _ in rows], jnp.int32)
                pos = jnp.asarray([p for _, _, p in rows], jnp.int32)
                z = jnp.zeros(len(rows), jnp.int32)
                eos = jnp.full((len(rows),), -1, jnp.int32)
                _t, _e, self.dkv.pools, _a = self._dstep(
                    self.draft_params, self.dkv.pools, {"tokens": toks},
                    pos, tables, eos, z, z)
                self.n_draft_forwards += 1
            for s in {r[0] for r in rows}:
                self._dnext[s] += sum(1 for sl, _, _ in rows if sl == s)
                seq = np.asarray(self._seq[s])
                self.dkv.register_filled(
                    s, seq, min(int(self._dnext[s]), len(seq)))

    def _spec_ready(self) -> bool:
        """A speculative round covers EVERY active slot (one verify batch,
        one plan); fall back to a base step unless all slots are in
        steady-state decode with headroom for k+1 more positions."""
        if self.n_active == 0:
            return False
        for s in range(self.n_active):
            r = self.active[s]
            if not r.out or int(self._prefill_next[s]) < len(self._seq[s]):
                return False                      # still prefilling
            if int(self.pos[s]) + self.spec_k + 1 >= self.capacity:
                return False                      # no room to speculate
            if int(self._dnext[s]) != int(self.pos[s]):
                return False                      # draft not caught up
        return True

    # -- the speculative round -----------------------------------------
    def step(self) -> int:
        if self.n_active == 0:
            return 0
        self._draft_catch_up()
        if not self._spec_ready():
            return super().step()
        t0 = self._clock()
        n = self._step_spec()
        if n:
            dt = self._clock() - t0
            self._ewma_step_s = dt if self._ewma_step_s is None \
                else 0.7 * self._ewma_step_s + 0.3 * dt
        return n

    def _step_spec(self) -> int:
        n, k = self.n_active, self.spec_k
        obs, i_step = self.obs, self._step_idx
        obs.step_begin(i_step)
        reqs = self.active[:n]
        pos0 = self.pos[:n].astype(np.int64).copy()
        with obs.tracer.step("serve/step", i_step, active=n, spec_k=k):
            seeds = jnp.asarray([self._req_seed(r) for r in reqs],
                                jnp.int32)
            counters = jnp.asarray([len(r.out) for r in reqs], jnp.int32)
            # -- draft: chain k proposals, no host sync between them
            with obs.tracer.span("serve/spec_draft", proposals=n * k):
                for s in range(n):
                    # target writes KV at [pos, pos+k]; draft at
                    # [pos, pos+k-1] (the k-th proposal is never fed back)
                    self.kv.ensure_allocated(s, int(pos0[s]) + k)
                    self.dkv.ensure_allocated(s, int(pos0[s]) + k - 1)
                dtables = jnp.asarray(self.dkv.table_rows(list(range(n))))
                cur = jnp.asarray([[r.out[-1]] for r in reqs], jnp.int32)
                dtoks, qdists = [], []
                for t in range(k):
                    dpos = jnp.asarray(pos0 + t, jnp.int32)
                    tok, q, self.dkv.pools, _ = self._draft_step(
                        self.draft_params, self.dkv.pools,
                        {"tokens": cur}, dpos, dtables, seeds,
                        counters + t)
                    dtoks.append(tok)
                    qdists.append(q)
                    cur = tok[:, None]
                    self.n_draft_forwards += 1
                draft_tok = jnp.stack(dtoks, axis=1)          # (n, k)
                draft_q = jnp.stack(qdists, axis=1)           # (n, k, V)
            # -- verify: ONE target forward over all n·(k+1) rows
            with obs.tracer.span("serve/spec_verify", tokens=n * (k + 1)):
                last = jnp.asarray([[r.out[-1]] for r in reqs], jnp.int32)
                vtok = jnp.concatenate([last, draft_tok],
                                       axis=1).reshape(n * (k + 1), 1)
                vpos = (pos0[:, None]
                        + np.arange(k + 1)[None, :]).reshape(-1)
                vtables = np.repeat(self.kv.table_rows(list(range(n))),
                                    k + 1, axis=0)
                emitted, n_emit, self.kv.pools, aux = self._verify_step(
                    self.params, self.kv.pools, self._batch(vtok),
                    jnp.asarray(vpos, jnp.int32), jnp.asarray(vtables),
                    draft_tok, draft_q, seeds, counters)
                self.n_forwards += 1
            with obs.tracer.span("serve/host_sync"):   # the ONE host sync
                em_np, ne_np = jax.device_get((emitted, n_emit))
            t_now = self._clock()
            with obs.tracer.span("serve/postprocess"):
                acc_round = 0
                emitted = []
                for s in range(n):
                    r = reqs[s]
                    self._last_aux[r.rid] = aux
                    ne, m = int(ne_np[s]), 0
                    for j in range(ne):
                        if len(r.out) >= r.max_new:
                            break
                        tok = int(em_np[s, j])
                        self._emit(r, tok, t_now, emitted)
                        m += 1
                        if r.eos is not None and tok == r.eos:
                            break
                    # rollback: both pools truncate to the new length —
                    # rejected rows die host-side (whole blocks freed)
                    new_pos = int(pos0[s]) + m
                    self.pos[s] = new_pos
                    self.kv.truncate_slot(s, new_pos)
                    dn = min(int(pos0[s]) + k, new_pos)
                    self.dkv.truncate_slot(s, dn)
                    self._dnext[s] = dn
                    self.n_drafted += k
                    acc_round += max(0, min(m, ne - 1))
                self._stream(emitted)
                self.n_accepted += acc_round
                self.n_spec_rounds += 1
                if obs.metered:
                    obs.metrics.inc("spec/rounds")
                    obs.metrics.inc("spec/drafted", n * k)
                    obs.metrics.inc("spec/accepted", acc_round)
                    obs.metrics.set_gauge("spec/acceptance_rate",
                                          self.acceptance_rate)
                # retire top-down so compaction never moves an unexamined
                # slot; the emit loop already stopped at EOS/max_new
                for s in range(n - 1, -1, -1):
                    r = self.active[s]
                    if (r.eos is not None and r.out and r.out[-1] == r.eos) \
                            or len(r.out) >= r.max_new \
                            or self.pos[s] >= self.capacity - 1:
                        self._retire(s, decode_batch=n)
            self._end_step(i_step, tokens=n * (k + 1))
        return n * (k + 1)
