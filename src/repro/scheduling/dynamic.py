"""``dynamic`` policy: adaptive block-to-expert assignment under routing skew.

The paper names this as future work: its fixed-``BLOCK_M`` layout
underperforms Megablocks' block-sparse layout at 64+ experts under extreme
Zipfian skew, because every light expert pads its partial tile up to a full
``block_m`` rows.  This policy removes that waste while keeping every
``BlockSchedule`` invariant the kernels rely on:

1. **Adaptive per-expert block sizing.**  The physical grid runs on
   sub-blocks of ``q = largest divisor of block_m <= block_m_min`` rows
   (q >= 8 keeps the f32 sublane tiling).  Each expert's segment is padded
   to an adaptively selected alignment — full ``block_m`` tiles for *heavy*
   experts (counts >= block_m, where MXU-shaped tiles matter), ``q`` rows
   for *light* ones — so per-expert padding is

       heavy: round_up(c, block_m)   (identical to ``fixed``)
       light: round_up(c, q)         (<= fixed's round_up(c, block_m))

   and total padded rows are <= the ``fixed`` policy's on EVERY assignment,
   strictly lower whenever any light expert has a partial tile (the Zipf
   regime: asserted in tests/test_scheduling_policies.py).

2. **Greedy bin-packing of expert segments.**  Segments are laid out in
   decreasing-load order (first-fit-decreasing on the block line).  All
   heavy segments therefore come first and — being block_m-multiples
   summed — start M-aligned, preserving the paper's full-tile property
   exactly where the FLOPs are; the light tail packs many small q-aligned
   segments into what ``fixed`` would spend on per-expert padding tiles,
   i.e. light experts share padding.  Heavy experts own proportionally more
   of the (now finer) block list: blocks-per-expert = padded_c / q.

3. **A static envelope sized to the padding it can reach.**  The grid is
   static, so the capacity is a worst case over every routing of T*k
   assignments to E experts (``dynamic_capacity``).  A light expert
   (0 < c < block_m) wastes at most q-1 rows; a heavy one at most
   block_m-1.  At most min(E, T*k) experts get a route and at most
   min(E, floor(T*k/block_m)) of them are heavy, so the padded total
   never exceeds

       B = T*k + min(E, T*k)*(q-1) + min(E, floor(T*k/block_m))*(block_m-q)

   Capacity is min(fixed's envelope, round_up(B, 8*q)): at decode-sized
   T it is a few dozen sub-blocks where fixed's E*block_m term alone is
   E*block_m/q of them, and at large T it never exceeds fixed's.  The
   8*q rounding keeps the permute/unpermute row blocks at 64-128 rows.
   The active blocks are packed from row 0, so shrinking the envelope
   only drops trailing inactive blocks.

Everything is jnp on-device (argsort / cumsum / searchsorted) — no host
round-trip, so the TPU no-host-sync property of the fixed policy is
preserved; the envelope depends only on static shapes, so jit shapes are
load-independent.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.scheduling.base import BlockSchedule, register_policy, round_up
from repro.scheduling.fixed import schedule_capacity


def sub_block(block_m: int, block_m_min: int = 8) -> int:
    """Largest divisor of block_m that is <= block_m_min AND keeps the
    8-row f32 sublane alignment (the Pallas kernels run on these blocks).
    When no such divisor exists (block_m not a multiple of 8), returns
    block_m itself: dynamic degrades to fixed alignment rather than ever
    emitting a TPU-misaligned tile.  block_m_min below 8 is clamped up to
    8 — never silently disable sub-tiling because the floor was small."""
    for q in range(max(min(block_m_min, block_m), 8), 7, -1):
        if block_m % q == 0 and q % 8 == 0:
            return q
    return block_m


def dynamic_capacity(n_tokens: int, top_k: int, n_experts: int,
                     block_m: int, q: int) -> int:
    """Static row envelope of a ``dynamic`` schedule: the padded total B
    it can reach on any routing (module docstring, 3.), rounded up to
    8*q rows, and never above the ``fixed`` policy's envelope."""
    n = n_tokens * top_k
    reach = (n + min(n_experts, n) * (q - 1)
             + min(n_experts, n // block_m) * (block_m - q))
    return min(schedule_capacity(n_tokens, top_k, n_experts, block_m),
               round_up(reach, 8 * q))


@register_policy("dynamic", config_fields=("block_m_min",))
def build_dynamic_schedule(indices: jnp.ndarray, n_experts: int,
                           block_m: int, *,
                           block_m_min: int = 8) -> BlockSchedule:
    T, k = indices.shape
    E, M = n_experts, block_m
    q = sub_block(M, block_m_min)
    capacity = dynamic_capacity(T, k, E, M, q)
    num_blocks = capacity // q

    flat = indices.reshape(-1).astype(jnp.int32)
    sort_idx = jnp.argsort(flat, stable=True)
    counts = jnp.bincount(flat, length=E).astype(jnp.int32)

    # (1) adaptive per-expert alignment: M-tiles where compute is dense,
    # q-sub-blocks where fixed would mostly pad
    heavy = counts >= M
    padded_counts = jnp.where(heavy,
                              (counts + M - 1) // M * M,
                              (counts + q - 1) // q * q).astype(jnp.int32)

    # (2) greedy decreasing packing: heavy experts first (M-aligned bases),
    # light experts share the q-granular tail
    order = jnp.argsort(-counts, stable=True).astype(jnp.int32)
    padded_ord = padded_counts[order]
    ends_ord = jnp.cumsum(padded_ord).astype(jnp.int32)
    starts_ord = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), ends_ord]).astype(jnp.int32)
    seg_start = jnp.zeros((E,), jnp.int32).at[order].set(starts_ord[:-1])

    unpadded_starts = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(counts)]).astype(jnp.int32)
    ranks = jnp.arange(T * k, dtype=jnp.int32)
    expert_sorted = flat[sort_idx]
    dest = (seg_start[expert_sorted]
            + ranks - unpadded_starts[expert_sorted])

    pos = jnp.zeros((T * k,), jnp.int32).at[sort_idx].set(dest).reshape(T, k)
    src_tok = jnp.full((capacity,), -1, jnp.int32).at[dest].set(
        sort_idx // k, mode="drop")

    block_starts = jnp.arange(num_blocks, dtype=jnp.int32) * q
    pos_in_order = jnp.searchsorted(ends_ord, block_starts, side="right")
    block_expert = order[jnp.minimum(pos_in_order, E - 1)]
    total_padded = ends_ord[-1] if E > 0 else jnp.int32(0)
    block_active = (block_starts < total_padded).astype(jnp.int32)

    return BlockSchedule(
        counts=counts,
        group_offsets=starts_ord,      # packing order; per-expert: seg_start
        src_tok=src_tok,
        pos=pos,
        block_expert=block_expert,
        block_active=block_active,
        capacity=capacity,
        block_m=q,
        seg_start=seg_start,
    )
