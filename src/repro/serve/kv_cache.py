"""Paged KV cache: a global block pool + per-slot block tables.

vLLM's memory model scaled to this container (DESIGN.md §9).  The serving
cache is no longer one contiguous ``(slots, capacity)`` buffer: the device
holds a pool of ``n_blocks`` fixed-size blocks per cache leaf — the SAME
pytree ``init_cache`` builds, with (batch=n_blocks, seq=block_size) — and
each slot owns a host-side *block table* mapping its logical block index
``pos // block_size`` to a physical block id.  The forward pass reads
through the table with plain jnp gathers (models/attention.py
``gather_block_kv``) and writes with per-token block-granular scatters
(``scatter_block_rows``, the paged sibling of ``scatter_decode_row``).

Control plane is host-side numpy/python (allocation, refcounts, hashes);
data plane is device arrays.  That split is deliberate: block management
runs once per engine step over a handful of ints, while every traced step
sees only dense int32 table rows — no host sync inside jit.

**Prefix caching.**  Full prompt blocks are content-addressed by a CHAINED
hash (block i's digest covers tokens [0, (i+1)*block_size)), so a hit
means the entire prefix matches, not just one block's tokens.  Hit blocks
are attached to the new slot's table and refcounted; their KV is never
recomputed and the tokens they cover never enter a dispatch plan (the
engine starts chunked prefill at ``n_cached``).  Only FULL prompt blocks
are ever shared, so shared blocks are immutable — decode appends always
land in slot-private blocks and copy-on-write is never needed.  Retirement
decrements refcounts; refcount-0 blocks that carry a registered hash are
parked in an LRU "cached free" pool (contents preserved for future hits)
and are evicted only when a fresh allocation finds the free list empty.

**Invariant.**  ``n_blocks = slots * ceil(capacity / block_size)`` — the
worst case (no sharing) is exactly the contiguous layout's footprint, and
sharing strictly frees blocks, so allocation can never fail.
"""
from __future__ import annotations

import hashlib
from collections import OrderedDict
from typing import Dict, List

import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models.lm import group_structure, init_cache

# block kinds whose caches are positional KV rows — the only thing a block
# pool can page.  Recurrent state (rwkv/ssm) and the fixed image KV of the
# vlm cross blocks have no sequence axis to page over; those families fall
# back to the contiguous engine.
PAGED_KINDS = frozenset(
    {"attn", "attn_local", "attn_global", "moe", "moe_dense"})


def paged_supported(cfg: ModelConfig) -> bool:
    """True when every layer's cache is positional KV (pageable)."""
    prefix, body, _, suffix = group_structure(cfg)
    return all(k in PAGED_KINDS for k in (*prefix, *body, *suffix))


def _chain_digest(prev: bytes, block_tokens: np.ndarray) -> bytes:
    """Chained content hash: covers the whole prefix up to this block."""
    return hashlib.sha256(prev + np.ascontiguousarray(
        block_tokens.astype(np.int32)).tobytes()).digest()


class PagedKVCache:
    """Block pool + per-slot tables + refcounted prefix index."""

    def __init__(self, cfg: ModelConfig, slots: int, capacity: int,
                 block_size: int, *, prefix_cache: bool = True,
                 dtype=jnp.float32):
        if block_size <= 0:
            raise ValueError(f"block_size must be positive, got {block_size}")
        if not paged_supported(cfg):
            raise ValueError(
                "paged KV cache needs every layer cache to be positional "
                f"KV; {cfg.name!r} has non-pageable (recurrent/cross) "
                "block caches — use the contiguous engine (kv_block_size=0)")
        self.cfg = cfg
        self.slots = slots
        self.capacity = capacity
        self.block_size = block_size
        self.blocks_per_slot = -(-capacity // block_size)
        self.n_blocks = slots * self.blocks_per_slot
        # the pool IS an init_cache pytree with (batch=n_blocks,
        # seq=block_size): every slot-view helper and the forward scan
        # consume it unchanged — the block axis simply replaces the slot
        # axis (0 for prefix/suffix leaves, 1 for the stacked body).
        self.pools = init_cache(cfg, self.n_blocks, block_size, dtype)
        self.tables = np.zeros((slots, self.blocks_per_slot), np.int32)
        self.n_alloc = np.zeros(slots, np.int32)      # allocated entries/slot
        self.refcount = np.zeros(self.n_blocks, np.int64)
        self.free: List[int] = list(range(self.n_blocks - 1, -1, -1))
        self.prefix_cache = prefix_cache
        self._hash_to_block: Dict[bytes, int] = {}
        self._block_hash: Dict[int, bytes] = {}
        # refcount-0 blocks with preserved contents, LRU eviction order
        self._cached_free: "OrderedDict[int, None]" = OrderedDict()
        # per-slot chained-hash cursor for registering blocks as they fill:
        # (next block index to register, digest of the chain before it)
        self._chain: Dict[int, tuple] = {}
        # preempted requests' parked tables (key -> {table, n_alloc, chain}),
        # LRU order: blocks stay refcounted (contents pinned) until the
        # request resumes or allocation pressure reclaims the record
        self._parked: "OrderedDict[object, dict]" = OrderedDict()
        # read-only probe memo (admission policies re-probe every step):
        # key -> (index generation, cached token count); any hash-index
        # mutation bumps the generation and drops the whole memo
        self._probe_gen = 0
        self._probe_memo: Dict[object, tuple] = {}
        self.hits = self.misses = self.evictions = 0
        self.park_reclaims = 0
        self.hit_tokens = 0
        # observability sinks (repro.obs; null by default — bind_obs()):
        # block alloc/evict/compaction become counters + trace instants
        from repro.obs import NULL_METRICS, NULL_TRACER
        self._metrics = NULL_METRICS
        self._tracer = NULL_TRACER

    def bind_obs(self, metrics, tracer) -> None:
        """Attach metrics/tracer sinks (the engine binds its bundle).
        Pool events are host-side control-plane work, so instrumenting
        them never touches the traced step."""
        self._metrics = metrics
        self._tracer = tracer

    # -- allocation ----------------------------------------------------
    def _index_mutated(self) -> None:
        """The hash index changed: read-only probe results are stale."""
        self._probe_gen += 1
        self._probe_memo.clear()

    def _alloc_block(self) -> int:
        if self.free:
            self._metrics.inc("kv/blocks_allocated")
            return self.free.pop()
        while not self._cached_free and self._parked:
            self._reclaim_parked()     # may refill free OR cached_free
            if self.free:
                self._metrics.inc("kv/blocks_allocated")
                return self.free.pop()
        if not self._cached_free:
            raise RuntimeError("paged pool exhausted — broken refcounting "
                               "(n_blocks guarantees worst-case capacity)")
        b, _ = self._cached_free.popitem(last=False)   # evict LRU
        digest = self._block_hash.pop(b)
        del self._hash_to_block[digest]
        self._index_mutated()
        self.evictions += 1
        self._metrics.inc("kv/blocks_allocated")
        self._metrics.inc("kv/evictions")
        self._tracer.instant("kv/evict", block=b)
        return b

    def _release_blocks(self, table: np.ndarray, n_alloc: int) -> None:
        for j in range(n_alloc):
            b = int(table[j])
            self.refcount[b] -= 1
            if self.refcount[b] == 0:
                if b in self._block_hash:
                    self._cached_free[b] = None    # park: contents reusable
                else:
                    self.free.append(b)

    def _reclaim_parked(self) -> None:
        """Allocation pressure: sacrifice the LRU parked (preempted) table
        so decoding slots never starve.  The victim's resume will find no
        record and falls back to re-prefill — strictly a latency cost,
        never a correctness one."""
        key, rec = self._parked.popitem(last=False)
        self._release_blocks(rec["table"], rec["n_alloc"])
        self.park_reclaims += 1
        self._metrics.inc("kv/park_reclaims")
        self._tracer.instant("kv/park_reclaim", key=str(key))

    def ensure_allocated(self, slot: int, last_pos: int) -> None:
        """Grow ``slot``'s table so position ``last_pos`` is addressable.

        Positions at/past the slot's addressable capacity get no block —
        their writes are DROPPED by ``scatter_block_rows`` (OOB scatter
        semantics), exactly like the contiguous cache's out-of-bounds
        decode write at the capacity edge; the engine's ``capacity - 1``
        retirement rule fires on the same step.  Whole prompts are
        validated against capacity at admission instead."""
        need = min(last_pos // self.block_size + 1, self.blocks_per_slot)
        while self.n_alloc[slot] < need:
            b = self._alloc_block()
            self.tables[slot, self.n_alloc[slot]] = b
            self.refcount[b] += 1
            self.n_alloc[slot] += 1

    # -- prefix caching ------------------------------------------------
    def attach_prefix(self, slot: int, prompt: np.ndarray) -> int:
        """Admission-time lookup: attach the longest run of hash-hit full
        prompt blocks to ``slot``; returns the number of cached TOKENS.

        At least one prompt token is always left uncached — its logits
        seed the first generated token — so a fully-cached prompt still
        runs a one-token chunk."""
        bs = self.block_size
        prompt = np.asarray(prompt)
        max_full = min((len(prompt) - 1) // bs, self.blocks_per_slot)
        digest = b""
        n_hit = 0
        if self.prefix_cache:
            with self._tracer.span("serve/prefix_probe", slot=slot,
                                   prompt_tokens=len(prompt)):
                for i in range(max_full):
                    nxt = _chain_digest(digest, prompt[i * bs:(i + 1) * bs])
                    b = self._hash_to_block.get(nxt)
                    if b is None:
                        self.misses += 1
                        self._metrics.inc("kv/prefix_misses")
                        break
                    digest = nxt
                    if self.refcount[b] == 0:           # revive parked block
                        self._cached_free.pop(b)
                    self.refcount[b] += 1
                    self.tables[slot, i] = b
                    self.n_alloc[slot] += 1
                    self.hits += 1
                    self._metrics.inc("kv/prefix_hits")
                    n_hit = i + 1
        self._chain[slot] = (n_hit, digest)
        self.hit_tokens += n_hit * bs
        self._metrics.inc("kv/prefix_hit_tokens", n_hit * bs)
        return n_hit * bs

    def probe_prefix(self, prompt: np.ndarray, *, memo_key=None) -> int:
        """Read-only lookup: how many TOKENS of ``prompt`` the index can
        currently serve from shared blocks (no attach, no refcounts) —
        what admission policies consult to prefer warm-prefix requests.

        ``memo_key`` (typically the request's rid) memoizes the answer
        until the hash index next mutates: admission policies probe every
        pending request every scheduling pass, and without the memo each
        pass re-hashes every pending prompt from scratch."""
        if not self.prefix_cache:
            return 0
        if memo_key is not None:
            hit = self._probe_memo.get(memo_key)
            if hit is not None and hit[0] == self._probe_gen:
                return hit[1]
        bs = self.block_size
        prompt = np.asarray(prompt)
        max_full = min((len(prompt) - 1) // bs, self.blocks_per_slot)
        digest = b""
        n = 0
        for i in range(max_full):
            digest = _chain_digest(digest, prompt[i * bs:(i + 1) * bs])
            if digest not in self._hash_to_block:
                break
            n = i + 1
        if memo_key is not None:
            self._probe_memo[memo_key] = (self._probe_gen, n * bs)
        return n * bs

    def register_filled(self, slot: int, prompt: np.ndarray,
                        n_processed: int) -> None:
        """Register every newly FULL prompt block of ``slot`` (called after
        a prefill chunk lands; ``n_processed`` counts prompt tokens whose
        KV is now written).  Content-addressing stays valid because block
        KV depends only on the token prefix (greedy, fixed params)."""
        if not self.prefix_cache or slot not in self._chain:
            return
        with self._tracer.span("serve/prefix_register", slot=slot):
            bs = self.block_size
            i, digest = self._chain[slot]
            while (i + 1) * bs <= n_processed:
                digest = _chain_digest(digest, prompt[i * bs:(i + 1) * bs])
                b = int(self.tables[slot, i])
                if digest not in self._hash_to_block:
                    self._hash_to_block[digest] = b
                    self._block_hash[b] = digest
                    self._index_mutated()
                i += 1
            self._chain[slot] = (i, digest)

    def truncate_slot(self, slot: int, n_tokens: int) -> int:
        """Roll ``slot``'s logical sequence back to its first ``n_tokens``
        positions — the speculative-decoding rollback (DESIGN.md §13):
        rejected draft tokens' KV lives in positions >= n_tokens, and
        dropping it is pure host-side bookkeeping.

        Whole blocks past ``ceil(n_tokens / block_size)`` are released
        (refcount decrement; a refcount-0 block with a registered hash
        parks in the LRU cached-free pool exactly like retirement).  The
        kept tail block may still hold stale rows past ``n_tokens`` —
        harmless: reads mask by each row's own kv_limit and the next
        write at that position scatters over them in place.  Prefix-hash
        registration past the truncation point is invalidated by
        rewinding the slot's chain cursor (registered hashes only ever
        cover full PROMPT blocks, which a speculative rollback never
        cuts into — the defensive drop below covers direct callers).
        Returns the number of blocks freed."""
        keep = 0 if n_tokens <= 0 else min(-(-n_tokens // self.block_size),
                                           self.blocks_per_slot)
        na = int(self.n_alloc[slot])
        if keep >= na:
            return 0
        for j in range(keep, na):
            b = int(self.tables[slot, j])
            self.refcount[b] -= 1
            if self.refcount[b] == 0:
                if b in self._block_hash:
                    self._cached_free[b] = None
                else:
                    self.free.append(b)
        self.tables[slot, keep:na] = 0
        self.n_alloc[slot] = keep
        ch = self._chain.get(slot)
        if ch is not None and ch[0] > keep:
            # the chain digest past ``keep`` covers tokens that no longer
            # exist; it cannot be rewound (digests chain forward only) —
            # stop registering for this slot rather than register stale
            # content
            del self._chain[slot]
        freed = na - keep
        self._metrics.inc("kv/blocks_truncated", freed)
        self._tracer.instant("kv/truncate", slot=slot, n_tokens=n_tokens,
                             freed=freed)
        return freed

    # -- release / park / views ----------------------------------------
    def release_slot(self, slot: int) -> None:
        self._release_blocks(self.tables[slot], int(self.n_alloc[slot]))
        self.tables[slot, :] = 0
        self.n_alloc[slot] = 0
        self._chain.pop(slot, None)

    def park_slot(self, slot: int, key) -> None:
        """Preemption: detach ``slot``'s table into a parked record under
        ``key`` (the request's rid).  Blocks KEEP their refcounts, so the
        request's KV survives intact for a host-side-only resume; under
        allocation pressure the LRU record is reclaimed instead (the
        resume then re-prefills).  The slot itself leaves empty."""
        self._parked[key] = {"table": self.tables[slot].copy(),
                             "n_alloc": int(self.n_alloc[slot]),
                             "chain": self._chain.get(slot)}
        self.tables[slot, :] = 0
        self.n_alloc[slot] = 0
        self._chain.pop(slot, None)
        self._metrics.inc("kv/tables_parked")
        self._tracer.instant("kv/park", slot=slot, key=str(key))

    def resume_slot(self, slot: int, key) -> bool:
        """Re-attach the parked table under ``key`` to (empty) ``slot``.
        False when the record was reclaimed for allocation pressure — the
        caller must re-prefill instead."""
        rec = self._parked.pop(key, None)
        if rec is None:
            return False
        assert self.n_alloc[slot] == 0, "resume target slot must be empty"
        self.tables[slot] = rec["table"]
        self.n_alloc[slot] = rec["n_alloc"]
        if rec["chain"] is not None:
            self._chain[slot] = rec["chain"]
        self._metrics.inc("kv/tables_resumed")
        self._tracer.instant("kv/resume", slot=slot, key=str(key))
        return True

    def drop_parked(self, key) -> None:
        """Discard a parked record (the request will never resume)."""
        rec = self._parked.pop(key, None)
        if rec is not None:
            self._release_blocks(rec["table"], rec["n_alloc"])

    def move_slot(self, dst: int, src: int) -> None:
        """Host-side slot compaction (the paged analogue of the contiguous
        engine's device row swap): tables are bookkeeping, so moving a
        request between slots is two numpy row writes."""
        self.tables[dst] = self.tables[src]
        self.n_alloc[dst] = self.n_alloc[src]
        if src in self._chain:
            self._chain[dst] = self._chain.pop(src)
        elif dst in self._chain:
            del self._chain[dst]
        self.tables[src] = 0
        self.n_alloc[src] = 0
        self._metrics.inc("kv/compactions")
        self._tracer.instant("kv/compaction", src=src, dst=dst)

    def table_rows(self, slot_ids) -> np.ndarray:
        """(len(slot_ids), blocks_per_slot) int32 rows for a step batch."""
        return self.tables[np.asarray(slot_ids, np.int64)]

    # -- metamorphic helper (tests/benchmarks) -------------------------
    def permute_physical_blocks(self, perm) -> None:
        """Relabel physical block ids: new id of block ``b`` is
        ``perm[b]``.  Pool contents move with their ids (device gather) and
        every host structure is remapped — greedy tokens must be invariant
        (asserted in tests/test_serve.py): the table indirection is the
        ONLY consumer of physical ids."""
        perm = np.asarray(perm, np.int64)
        assert sorted(perm.tolist()) == list(range(self.n_blocks))
        inv = jnp.asarray(np.argsort(perm), jnp.int32)
        from repro.models.lm import _map_cache
        self.pools = _map_cache(
            lambda ax, l: jnp.take(l, inv, axis=ax), self.pools)
        self.tables = perm[self.tables].astype(np.int32)
        self.refcount = self.refcount[np.argsort(perm)]
        self.free = [int(perm[b]) for b in self.free]
        self._hash_to_block = {h: int(perm[b])
                               for h, b in self._hash_to_block.items()}
        self._block_hash = {int(perm[b]): h
                            for b, h in self._block_hash.items()}
        self._cached_free = OrderedDict(
            (int(perm[b]), None) for b in self._cached_free)
        for rec in self._parked.values():
            rec["table"] = perm[rec["table"]].astype(np.int32)

    # -- introspection -------------------------------------------------
    def stats(self) -> dict:
        in_use = int((self.refcount > 0).sum())
        return {"blocks_total": self.n_blocks, "blocks_in_use": in_use,
                "blocks_parked": len(self._cached_free),
                "prefix_hits": self.hits, "prefix_misses": self.misses,
                "prefix_hit_tokens": self.hit_tokens,
                "evictions": self.evictions,
                "parked_tables": len(self._parked),
                "park_reclaims": self.park_reclaims}
