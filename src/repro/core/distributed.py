"""Expert-parallel MoE dispatch over a mesh axis (beyond-paper).

The paper targets single-GPU dispatch and defers multi-device expert
parallelism (its Limitation 6).  Here the paper's pipeline becomes the
*per-device inner loop* of a GShard-style EP layer.  EP is an *executor
wrapper*, not a forked pipeline: each rank consumes a `DispatchPlan`
(routing built once by ``plan_dispatch`` — never re-derived locally) and
composes the configured executor's phase methods (permute / expert_ffn /
unpermute, repro.execution) over a rank-local layout.  Any schedule-capable
backend works under EP unchanged; only the layout between the phases is
EP-specific:

``token_layout="sharded"`` (train / prefill / batch-sharded decode — tokens
are split over the EP axis), the **padding-free send path** (X-MoE style):
  plan (local router) -> policy drop decisions on GLOBAL slot ranks ->
  per-destination-rank COMPACTED send buffers (no per-expert tile rounding:
  the transport is sized by the schedule policy's capacity, not
  ``E_local * static_cap``) -> int32 metadata all_to_all carrying each
  row's expert assignment (the receive side recovers true per-expert
  counts from it) + payload all_to_all -> the receive side builds a REAL
  ``BlockSchedule`` under ``cfg.schedule_policy`` (any schedule-capable
  executor runs unchanged) -> inverse all_to_all -> weighted combine on
  the source rank.  Dropped assignments are decided by the policy exactly
  as on a single device (global first-come-first-kept slot ranks) and flow
  into the ``sched/*`` aux stats.

``token_layout="sharded_static"`` — the legacy static capacity transport
(every expert gets a tile-aligned ``cap`` bucket in the a2a buffer,
``E_local * cap`` rows per destination regardless of load; assignments
beyond a bucket are silently dropped).  Kept for A/B measurement of the
padding-free path's payload win (benchmarks/serving_throughput.py
--ep-scaling); it ignores ``cfg.schedule_policy`` — which is the historic
bug the padding-free path fixes.

``token_layout="replicated"`` (decode — every EP rank sees the same tokens):
  each rank restricts the plan's routing to the experts it owns (non-owned
  assignments routed to an inactive sentinel expert whose blocks are
  skipped), runs executor permute/expert_ffn/unpermute on that local
  schedule, then a single psum over the EP axis combines partial outputs
  — the collective is O(B*d) instead of an all_to_all of expert buffers.

Drop semantics are the *schedule policy's* under every layout: ``fixed``
and ``dynamic`` drop nothing (the padding-free transport reserves the
worst-case per-destination send capacity so parity with single-device
dispatch is exact); ``capacity_factor`` drops beyond its per-expert bucket
sized over the GLOBAL token count, matching the single-device policy
row-for-row.  ``capacity_factor`` (the argument) resolves as documented in
``apply_moe_ep``; drop counts surface through the same ``sched/*`` aux
keys as single-device dispatch when ``cfg.emit_stats`` is set.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.dispatch import MoEDispatchConfig
from repro.execution import (combine_scale_rows, get_executor,
                             plan_dispatch)
from repro.scheduling import (BlockSchedule, ScheduleStats, build_schedule,
                              capacity_slots, expert_capacity,
                              policy_config_kwargs, round_up)

# Padding-free send buffers are lane-aligned to this row multiple (no
# per-expert block_m rounding — that is the whole point).
_SEND_ALIGN = 8


def _resolve_capacity_factor(cfg: MoEDispatchConfig,
                             capacity_factor: Optional[float]) -> float:
    """THE resolution order for EP capacity headroom (asserted by tests):
    an explicit ``capacity_factor`` argument wins; ``None`` falls back to
    ``cfg.capacity_factor`` (which ``dispatch_config`` defaults from the
    model's ``MoEConfig``).  No other source is consulted."""
    return cfg.capacity_factor if capacity_factor is None else capacity_factor


def a2a_send_rows(n_local_tokens: int, top_k: int, n_experts: int, ep: int,
                  block_m: int, capacity_factor: float, policy: str) -> int:
    """Per-destination-rank send-buffer rows of the padding-free path.

    Sized by the POLICY's capacity commitment, not by a per-expert static
    bucket: no-drop policies (fixed/dynamic) reserve the worst case — every
    local assignment routed to one destination — so parity with
    single-device dispatch is exact; ``capacity_factor`` is additionally
    bounded by the destination's post-drop acceptance
    (``E_local * cap_global``).  One rank's total a2a payload is
    ``ep * a2a_send_rows(...)`` rows (compare ``a2a_send_rows_static``).
    """
    F = n_local_tokens * top_k
    C = round_up(max(F, 1), _SEND_ALIGN)
    if policy == "capacity_factor":
        cap_g = expert_capacity(n_local_tokens * ep, top_k, n_experts,
                                block_m, capacity_factor)
        C = min(C, round_up((n_experts // ep) * cap_g, _SEND_ALIGN))
    return C


def a2a_send_rows_static(n_local_tokens: int, top_k: int, n_experts: int,
                         block_m: int, capacity_factor: float) -> int:
    """Total send rows of the legacy static-capacity transport
    (``token_layout='sharded_static'``): every expert gets a tile-aligned
    bucket whether or not any token routed to it."""
    return n_experts * expert_capacity(n_local_tokens, top_k, n_experts,
                                       block_m, capacity_factor)


def _static_schedule(n_rows: int, n_local_experts: int, block_m: int,
                     rows_per_expert: int) -> BlockSchedule:
    """Schedule for the legacy fixed EP receive layout: rows grouped by
    local expert with a static group size (rows_per_expert each).

    The ``rows_per_expert // block_m`` layout math silently misassigns
    ``block_expert`` when handed an unaligned capacity, so misalignment is
    a loud error here — callers must round capacity up to a ``block_m``
    multiple first (``round_up``)."""
    if rows_per_expert % block_m or n_rows % block_m:
        raise ValueError(
            f"static EP receive layout requires block_m-aligned capacity: "
            f"rows_per_expert={rows_per_expert}, n_rows={n_rows}, "
            f"block_m={block_m}; round capacity up with "
            f"scheduling.round_up before building the layout")
    nb = n_rows // block_m
    block_expert = (jnp.arange(nb, dtype=jnp.int32)
                    // (rows_per_expert // block_m))
    return BlockSchedule(
        counts=jnp.full((n_local_experts,), rows_per_expert, jnp.int32),
        group_offsets=jnp.arange(n_local_experts + 1, dtype=jnp.int32)
        * rows_per_expert,
        src_tok=jnp.zeros((n_rows,), jnp.int32),
        pos=jnp.zeros((1, 1), jnp.int32),
        block_expert=block_expert,
        block_active=jnp.ones((nb,), jnp.int32),
        capacity=n_rows, block_m=block_m)


def _rank_plan(params, x_loc, cfg: MoEDispatchConfig, axis: str):
    """Routing plan for this rank's tokens + EP-meaned aux.  One plan per
    batch; all layouts consume it instead of re-deriving routing."""
    plan = plan_dispatch(x_loc, params["router"], cfg, with_schedule=False)
    aux = {k: jax.lax.pmean(v, axis) for k, v in plan.aux.items()}
    return plan._replace(aux=aux)


def _ep_stats(axes, *, kept, dropped, counts_local, sched):
    """EP ScheduleStats under the single-device ``sched/*`` key contract.

    ``kept``/``dropped`` count this rank's SOURCE assignments (each
    assignment is counted on exactly one rank); padding cost comes from the
    rank's receive-side schedule; all totals psum over EVERY token-sharding
    axis (``axes``: the EP axis plus any batch-sharding axes) so each rank
    returns the same replicated global scalars."""
    useful = jax.lax.psum(kept.astype(jnp.int32), axes)
    dropped = jax.lax.psum(dropped.astype(jnp.int32), axes)
    n_active = jax.lax.psum(
        jnp.sum(sched.block_active.astype(jnp.int32)), axes)
    padded = n_active * sched.block_m
    counts_g = jax.lax.psum(counts_local.astype(jnp.int32), axes)
    total = jnp.sum(counts_g)
    f32 = jnp.float32
    safe = lambda a, b: a.astype(f32) / jnp.maximum(b, 1).astype(f32)
    st = ScheduleStats(
        useful_rows=useful, dropped_rows=dropped, padded_rows=padded,
        pad_waste=safe(padded, useful),
        drop_fraction=safe(dropped, useful + dropped),
        top1_share=safe(jnp.max(counts_g), total),
        n_blocks_active=n_active, occupancy=safe(useful, padded),
        grid_rows=jax.lax.psum(jnp.int32(sched.capacity), axes))
    return {f"sched/{k}": v for k, v in st._asdict().items()}


def _deactivate_sentinel(sched: BlockSchedule,
                         n_local_experts: int) -> BlockSchedule:
    """Turn the sentinel expert's blocks off so Pallas skips them on TPU
    (and the XLA scan zeroes their rows)."""
    return sched._replace(
        block_active=sched.block_active
        * (sched.block_expert < n_local_experts).astype(jnp.int32),
        block_expert=jnp.minimum(sched.block_expert, n_local_experts - 1))


def _recv_schedule(e_recv, cfg: MoEDispatchConfig, E_local: int,
                   cap_global: Optional[int]) -> BlockSchedule:
    """Receive-side schedule under the configured policy: E_local real
    experts + one sentinel absorbing transport padding rows.  The
    ``capacity_factor`` policy's bucket is pinned to the GLOBAL cap, so the
    send-side drop decisions are final (received counts never exceed it —
    the policy never drops twice)."""
    kw = policy_config_kwargs(cfg.schedule_policy, cfg)
    if cfg.schedule_policy == "capacity_factor":
        kw["cap"] = cap_global
    sched = build_schedule(e_recv[:, None], E_local + 1, cfg.block_m,
                           policy=cfg.schedule_policy, **kw)
    return _deactivate_sentinel(sched, E_local)


# ----------------------------------------------------------------------
# Padding-free sharded path (token_layout="sharded")
# ----------------------------------------------------------------------
def _capacity_keep(flat, gtok, Tl, k, E, ep, cap_global, axis):
    """Exact single-device first-come-first-kept for the capacity policy:
    gather every rank's (expert, global-token-order) assignment keys, rank
    slots in TRUE global token order, read back this rank's verdicts.  The
    gather is O(T*k) int32 — metadata scale, not the payload's.  ``gtok``
    (Tl,) holds each local row's global token id (any values whose order
    matches the unsharded flatten order), making the drop set invariant to
    which dim the tokens were split on."""
    F = Tl * k
    if gtok is None:
        gtok = jax.lax.axis_index(axis) * Tl \
            + jnp.arange(Tl, dtype=jnp.int32)
    gkey = gtok.astype(jnp.int32)[:, None] * k \
        + jnp.arange(k, dtype=jnp.int32)[None, :]            # (Tl, k)
    fa = jax.lax.all_gather(flat, axis).reshape(-1)          # (ep*F,)
    ga = jax.lax.all_gather(gkey.reshape(-1), axis).reshape(-1)
    perm = jnp.argsort(ga)                   # -> single-device token order
    slot_sorted, _ = capacity_slots(fa[perm], E)
    keep_all = jnp.zeros((ep * F,), bool).at[perm].set(
        slot_sorted < cap_global)
    r = jax.lax.axis_index(axis)
    return jax.lax.dynamic_slice_in_dim(keep_all, r * F, F)


def _sharded_send_phase(x_loc, cfg: MoEDispatchConfig, ep: int, plan, keep,
                        cap_global: Optional[int]):
    """Local half of the dispatch: compact this rank's KEPT assignments
    into per-destination send chunks.  Drop/keep was already decided by
    the policy over the FULL batch (``_capacity_keep``), so microbatching
    cannot change the drop set.  Returns (send (ep, C, d), e_send (ep, C)
    int32 local-expert ids with ``E_local`` marking transport padding,
    state dict for compute/combine)."""
    E, k = cfg.n_experts, cfg.top_k
    E_local = E // ep
    Tl, d = x_loc.shape
    F = Tl * k

    flat = plan.indices.reshape(-1).astype(jnp.int32)        # (F,) global e
    _, counts_local = capacity_slots(flat, E)

    # compacted per-destination send chunks: stable slot within the kept
    # rows headed to each destination rank (token-major inside a chunk).
    # C is the policy's transport commitment: worst case for no-drop
    # policies, bounded by the destination's post-drop acceptance for
    # capacity_factor (always using the FULL-batch cap).
    C = round_up(max(F, 1), _SEND_ALIGN)
    if cap_global is not None:
        C = min(C, round_up(E_local * cap_global, _SEND_ALIGN))
    dest_rank = flat // E_local
    dkey = jnp.where(keep, dest_rank, ep)                    # drops -> bin ep
    send_slot, _ = capacity_slots(dkey, ep + 1)
    tkeep = keep & (send_slot < C)     # C covers kept rows by construction
    send_pos = dkey * C + send_slot                          # row in send buf

    src_rows = jnp.repeat(jnp.arange(Tl, dtype=jnp.int32), k)
    oob = jnp.where(tkeep, send_pos, ep * C)
    send = jnp.zeros((ep * C, d), x_loc.dtype).at[oob].set(
        x_loc[src_rows], mode="drop")
    e_send = jnp.full((ep * C,), E_local, jnp.int32).at[oob].set(
        flat % E_local, mode="drop")

    state = dict(plan=plan, tkeep=tkeep, send_pos=send_pos,
                 counts_local=counts_local, cap_global=cap_global,
                 C=C, ep=ep, E_local=E_local, Tl=Tl, k=k, d=d)
    return send.reshape(ep, C, d), e_send.reshape(ep, C), state


def _sharded_compute_phase(recv, e_recv, cfg: MoEDispatchConfig, state):
    """Receive half: build the policy's BlockSchedule over the received
    rows (+ sentinel for transport padding) and run the executor phases."""
    from repro.quantization import expert_weights
    ex = get_executor(cfg.executor)
    d, E_local = state["d"], state["E_local"]
    rows = recv.reshape(-1, d)
    sched = _recv_schedule(e_recv.reshape(-1), cfg, E_local,
                           state["cap_global"])
    local_w = ex.prepare_weights(
        expert_weights(state["params"], rows.dtype), cfg)
    xp = ex.permute(rows, sched, cfg)
    y = ex.expert_ffn(xp, local_w, sched, cfg)
    y_rows = ex.unpermute(y, sched, None, cfg)               # (ep*C, d)
    return y_rows.reshape(state["ep"], state["C"], d), sched


def _sharded_combine_phase(back, cfg: MoEDispatchConfig, state):
    """Source-side weighted combine of the returned expert outputs."""
    ep, C, Tl, k, d = (state["ep"], state["C"], state["Tl"], state["k"],
                       state["d"])
    y = back.reshape(ep * C, d)
    gathered = y[jnp.minimum(state["send_pos"], ep * C - 1)]  # (Tl*k, d)
    w_eff = jnp.where(state["tkeep"],
                      state["plan"].weights.reshape(-1), 0.0)
    out = jnp.sum(gathered.reshape(Tl, k, d).astype(jnp.float32)
                  * w_eff.reshape(Tl, k, 1), axis=1)
    return out


def _a2a(v, axis: str):
    return jax.lax.all_to_all(v, axis, split_axis=0, concat_axis=0,
                              tiled=False)


def _ep_sharded_local(params, x_loc, cfg: MoEDispatchConfig, axis: str,
                      capacity_factor: float, n_micro: int = 1,
                      stat_axes=None, gtok=None):
    """Per-rank body for token_layout='sharded'. x_loc: (T_local, d).

    ``n_micro > 1`` software-pipelines the dispatch: the all_to_all of
    microbatch i+1 is issued BEFORE the expert GEMMs of microbatch i in
    the traced program, so XLA's async collective scheduler can overlap
    transport with compute (X-MoE double buffering).  ``n_micro == 1`` is
    the exact straight-line path — the pipeline degenerates to
    send -> a2a -> compute -> a2a -> combine with no extra ops.  Routing
    and the capacity policy's drop set are decided over the FULL batch
    before chunking, so the overlap path is token-identical to the
    non-overlapped one."""
    ep = jax.lax.axis_size(axis)
    E, k, M = cfg.n_experts, cfg.top_k, cfg.block_m
    if E % ep:
        raise ValueError(f"n_experts={E} must divide over EP axis size {ep}")
    Tl = x_loc.shape[0]
    while Tl % n_micro:
        n_micro -= 1                       # largest divisor <= requested
    c = Tl // n_micro
    chunks = [x_loc[i * c:(i + 1) * c] for i in range(n_micro)]
    plans = [_rank_plan(params, ch, cfg, axis) for ch in chunks]

    cap_global = None
    if cfg.schedule_policy == "capacity_factor":
        cap_global = expert_capacity(Tl * ep, k, E, M, capacity_factor)
        flat_full = jnp.concatenate(
            [p.indices.reshape(-1).astype(jnp.int32) for p in plans])
        keep_full = _capacity_keep(flat_full, gtok, Tl, k, E, ep,
                                   cap_global, axis)
        keeps = [keep_full[i * c * k:(i + 1) * c * k]
                 for i in range(n_micro)]
    else:
        keeps = [jnp.ones((c * k,), bool) for _ in range(n_micro)]

    sends = []
    for i, ch in enumerate(chunks):
        send, e_send, st = _sharded_send_phase(ch, cfg, ep, plans[i],
                                               keeps[i], cap_global)
        st["params"] = params
        sends.append((send, e_send, st))

    outs, auxes = [], []
    recv = (_a2a(sends[0][0], axis), _a2a(sends[0][1], axis))
    for i in range(n_micro):
        nxt = None
        if i + 1 < n_micro:                # issue i+1's a2a before GEMMs i
            nxt = (_a2a(sends[i + 1][0], axis), _a2a(sends[i + 1][1], axis))
        st = sends[i][2]
        y, sched = _sharded_compute_phase(recv[0], recv[1], cfg, st)
        back = _a2a(y, axis)
        outs.append(_sharded_combine_phase(back, cfg, st))
        aux = dict(st["plan"].aux)
        if cfg.emit_stats:
            kept = jnp.sum(st["tkeep"].astype(jnp.int32))
            aux.update(_ep_stats(
                stat_axes or (axis,), kept=kept,
                dropped=jnp.int32(st["tkeep"].shape[0]) - kept,
                counts_local=st["counts_local"], sched=sched))
        auxes.append(aux)
        recv = nxt

    out = jnp.concatenate(outs, axis=0) if n_micro > 1 else outs[0]
    aux = _merge_chunk_aux(auxes)
    return out.astype(x_loc.dtype), aux


def _merge_chunk_aux(auxes):
    """Combine per-microbatch aux: additive stats sum, ratios recompute,
    losses average — one chunk passes through untouched."""
    if len(auxes) == 1:
        return auxes[0]
    n = len(auxes)
    out = {}
    add = ("sched/useful_rows", "sched/dropped_rows", "sched/padded_rows",
           "sched/n_blocks_active", "sched/grid_rows")
    for k in auxes[0]:
        if k in add:
            out[k] = sum(a[k] for a in auxes)
        elif k == "sched/top1_share":
            out[k] = jnp.max(jnp.stack([a[k] for a in auxes]))
        elif k.startswith("sched/"):
            continue                        # ratios rebuilt below
        else:
            out[k] = sum(a[k] for a in auxes) / n
    if "sched/useful_rows" in out:
        f32 = jnp.float32
        safe = lambda a, b: a.astype(f32) / jnp.maximum(b, 1).astype(f32)
        u, dr = out["sched/useful_rows"], out["sched/dropped_rows"]
        out["sched/pad_waste"] = safe(out["sched/padded_rows"], u)
        out["sched/drop_fraction"] = safe(dr, u + dr)
        out["sched/occupancy"] = safe(u, out["sched/padded_rows"])
    return out


# ----------------------------------------------------------------------
# Legacy static-capacity transport (token_layout="sharded_static")
# ----------------------------------------------------------------------
def _ep_sharded_static_local(params, x_loc, cfg: MoEDispatchConfig,
                             axis: str, capacity_factor: float,
                             stat_axes=None):
    """The pre-padding-free a2a layout, kept for A/B payload measurement.
    Every expert gets a static tile-aligned ``cap`` bucket; assignments
    beyond it are dropped REGARDLESS of ``cfg.schedule_policy`` (the
    historic policy bypass)."""
    ep = jax.lax.axis_size(axis)
    E, k, M = cfg.n_experts, cfg.top_k, cfg.block_m
    E_local = E // ep
    Tl, d = x_loc.shape

    plan = _rank_plan(params, x_loc, cfg, axis)
    cap = round_up(expert_capacity(Tl, k, E, M, capacity_factor), M)

    flat = plan.indices.reshape(-1)                          # (Tl*k,)
    slot, counts_local = capacity_slots(flat, E)
    keep = slot < cap
    dest = flat * cap + slot                                 # row in send buf

    send = jnp.zeros((E * cap, d), x_loc.dtype)
    src_rows = jnp.repeat(jnp.arange(Tl), k)
    send = send.at[jnp.where(keep, dest, E * cap)].set(
        x_loc[src_rows], mode="drop")

    # (E*cap, d) -> (ep, E_local*cap, d) -> a2a -> rows from every peer
    recv = _a2a(send.reshape(ep, E_local * cap, d), axis)
    # regroup: (ep, E_local, cap, d) -> (E_local, ep*cap, d): contiguous
    # per local expert, group size ep*cap (tile-aligned since cap % M == 0)
    recv = recv.reshape(ep, E_local, cap, d).transpose(1, 0, 2, 3) \
        .reshape(E_local * ep * cap, d)

    from repro.quantization import expert_weights
    ex = get_executor(cfg.executor)
    sched = _static_schedule(E_local * ep * cap, E_local, M, ep * cap)
    local_w = ex.prepare_weights(expert_weights(params, x_loc.dtype), cfg)
    y = ex.expert_ffn(recv, local_w, sched, cfg)

    # inverse path
    y = y.reshape(E_local, ep, cap, d).transpose(1, 0, 2, 3) \
        .reshape(ep, E_local * cap, d)
    y = _a2a(y, axis).reshape(E * cap, d)

    gathered = y[jnp.minimum(dest, E * cap - 1)]             # (Tl*k, d)
    w_eff = jnp.where(keep, plan.weights.reshape(-1), 0.0)
    out = jnp.sum(gathered.reshape(Tl, k, d).astype(jnp.float32)
                  * w_eff.reshape(Tl, k, 1), axis=1)
    aux = dict(plan.aux)
    if cfg.emit_stats:
        kept = jnp.sum(keep.astype(jnp.int32))
        aux.update(_ep_stats(stat_axes or (axis,), kept=kept,
                             dropped=jnp.int32(Tl * k) - kept,
                             counts_local=counts_local, sched=sched))
    return out.astype(x_loc.dtype), aux


# ----------------------------------------------------------------------
# Replicated path (token_layout="replicated")
# ----------------------------------------------------------------------
def _ep_replicated_local(params, x_loc, cfg: MoEDispatchConfig, axis: str,
                         capacity_factor: float, stat_axes=None):
    """Per-rank body for token_layout='replicated' (decode)."""
    ep = jax.lax.axis_size(axis)
    E, M = cfg.n_experts, cfg.block_m
    E_local = E // ep
    r = jax.lax.axis_index(axis)
    base = r * E_local

    plan = _rank_plan(params, x_loc, cfg, axis)

    mine = (plan.indices >= base) & (plan.indices < base + E_local)
    # non-owned assignments -> sentinel expert E_local (blocks deactivated)
    idx_local = jnp.where(mine, plan.indices - base, E_local)
    w_masked = jnp.where(mine, plan.weights, 0.0)

    # the configured schedule policy, over the local experts plus one
    # sentinel "expert" that absorbs non-owned assignments; capacity buckets
    # must be sized over the GLOBAL expert count so EP drop semantics match
    # the single-device policy exactly
    kw = policy_config_kwargs(cfg.schedule_policy, cfg)
    cap = None
    if cfg.schedule_policy == "capacity_factor":
        cap = expert_capacity(x_loc.shape[0], cfg.top_k, E, M,
                              capacity_factor)
        kw["cap"] = cap
    sched = build_schedule(idx_local, E_local + 1, M,
                           policy=cfg.schedule_policy, **kw)
    sched = _deactivate_sentinel(sched, E_local)

    from repro.quantization import expert_weights
    ex = get_executor(cfg.executor)
    xp = ex.permute(x_loc, sched, cfg)
    scale = combine_scale_rows(sched, w_masked)
    local_w = ex.prepare_weights(expert_weights(params, x_loc.dtype), cfg)
    y = ex.expert_ffn(xp, local_w, sched, cfg, row_scale=scale)
    out = ex.unpermute(y, sched, None, cfg)
    out = jax.lax.psum(out.astype(jnp.float32), axis)
    aux = dict(plan.aux)
    if cfg.emit_stats:
        flat_mine = mine.reshape(-1)
        if cap is not None:
            slot, _ = capacity_slots(idx_local.reshape(-1), E_local + 1)
            dropped = jnp.sum((flat_mine & (slot >= cap)).astype(jnp.int32))
        else:
            dropped = jnp.int32(0)
        kept = jnp.sum(flat_mine.astype(jnp.int32)) - dropped
        counts_local = jnp.bincount(
            jnp.where(flat_mine, idx_local.reshape(-1) + base, E),
            length=E + 1)[:E]              # owned-expert counts only
        aux.update(_ep_stats(stat_axes or (axis,), kept=kept,
                             dropped=dropped,
                             counts_local=counts_local, sched=sched))
    return out.astype(x_loc.dtype), aux


# ----------------------------------------------------------------------
def apply_moe_ep(params, x: jnp.ndarray, cfg: MoEDispatchConfig, *,
                 axis: str = "model", capacity_factor: Optional[float] = None,
                 token_layout: str = "sharded", overlap: int = 0):
    """Distributed MoE layer. x: (B, S, d) inside jit (GSPMD context);
    the EP dispatch itself runs under shard_map over `axis`.

    ``capacity_factor`` resolution order (one rule, asserted by tests):
    **explicit argument > cfg.capacity_factor** — ``None`` means "use the
    config", anything else wins outright.  It feeds the
    ``capacity_factor`` schedule policy's drop buckets and the legacy
    ``sharded_static`` transport; the padding-free sharded path needs no
    separate headroom knob (its transport is sized by the policy's own
    capacity, see ``a2a_send_rows``).

    ``cfg.schedule_policy`` is honored by EVERY layout except the legacy
    ``sharded_static`` transport (kept only for payload A/B measurement):
    the sharded path builds the policy's ``BlockSchedule`` on the receive
    side of the all_to_all, the replicated path over its owned experts.
    Drop decisions match single-device dispatch row-for-row.

    ``overlap`` (sharded layout only): number of dispatch microbatches to
    software-pipeline — expert GEMMs of microbatch i overlap the
    all_to_all of i+1.  ``0``/``1`` = the straight-line path.

    The sharded layout splits tokens over ``axis`` on the sequence dim
    when ``S`` divides, else the batch dim (decode slots), else falls
    back to the replicated layout (always correct — tokens just aren't
    split).

    ``cfg.executor`` must name a schedule-capable backend (phase-level
    permute/expert_ffn/unpermute) — ``xla`` or ``pallas``; the ``dense``
    oracle has no permuted layout and raises under EP.

    Shared experts are dense compute on (sharded) tokens — they stay in
    plain GSPMD-land outside the shard_map.
    """
    capacity_factor = _resolve_capacity_factor(cfg, capacity_factor)
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty:
        raise RuntimeError("apply_moe_ep requires an active mesh "
                           "(jax.set_mesh(...))")
    if token_layout not in ("sharded", "sharded_static", "replicated"):
        raise ValueError(f"unknown token_layout {token_layout!r}")
    shape = x.shape
    d = shape[-1]
    other = [a for a in mesh.axis_names if a != axis]
    ep = mesh.shape[axis]
    bspec = tuple(other) if shape[0] % max(_axsize(mesh, other), 1) == 0 \
        else None
    # stats totals must span every axis tokens are split over: the EP axis
    # plus the batch-sharding axes (aux out_specs claim full replication)
    stat_axes = (tuple(bspec) if bspec else ()) + (axis,)

    if token_layout in ("sharded", "sharded_static") \
            and shape[1] % ep and shape[0] % ep:
        token_layout = "replicated"        # nothing divides: don't split

    if token_layout in ("sharded", "sharded_static"):
        seq_sharded = shape[1] % ep == 0
        if seq_sharded:
            in_spec = P(bspec, axis, None)     # seq-sharded (train/prefill)
        else:
            in_spec = P(axis, None, None)      # batch-sharded (decode slots)
        out_spec = in_spec

        def body(p_loc, x_loc):
            B_l, S_l, _ = x_loc.shape
            # global token ids in the unsharded (b, s) flatten order, so
            # policy drop decisions are sharding-invariant
            r = jax.lax.axis_index(axis)
            idx = jnp.arange(B_l * S_l, dtype=jnp.int32)
            if seq_sharded:
                gtok = (idx // S_l) * (S_l * ep) + r * S_l + idx % S_l
            else:
                gtok = r * (B_l * S_l) + idx
            if token_layout == "sharded":
                y, aux = _ep_sharded_local(p_loc, x_loc.reshape(-1, d), cfg,
                                           axis, capacity_factor,
                                           max(1, overlap),
                                           stat_axes=stat_axes, gtok=gtok)
            else:
                y, aux = _ep_sharded_static_local(
                    p_loc, x_loc.reshape(-1, d), cfg, axis, capacity_factor,
                    stat_axes=stat_axes)
            return y.reshape(B_l, S_l, d), aux
    else:
        in_spec = P(bspec, None, None)
        out_spec = P(bspec, None, None)

        def body(p_loc, x_loc):
            B_l, S_l, _ = x_loc.shape
            y, aux = _ep_replicated_local(p_loc, x_loc.reshape(-1, d), cfg,
                                          axis, capacity_factor,
                                          stat_axes=stat_axes)
            return y.reshape(B_l, S_l, d), aux

    from repro.execution import get_executor as _get_ex
    from repro.quantization import params_scheme
    scheme = params_scheme(params)
    if not _get_ex(cfg.executor).supports_scheme(scheme):
        raise ValueError(
            f"executor {cfg.executor!r} does not support quant scheme "
            f"{scheme!r} under EP")

    routed = {k_: v for k_, v in params.items() if k_ != "shared"}
    # expert tensors shard over the EP axis on their leading (expert)
    # axis.  Built per LEAF so quantized params work for ANY scheme: a
    # QuantTensor contributes its compressed payload + scale leaves (both
    # expert-leading), and each rank gathers only compressed bytes.
    pspecs = {k_: (P(None, None) if k_ == "router"
                   else jax.tree.map(
                       lambda l: P(axis, *([None] * (l.ndim - 1))), v))
              for k_, v in routed.items()}
    aux_spec = {"lb_loss": P(), "router_z": P()}
    if cfg.emit_stats:
        aux_spec.update({f"sched/{k}": P() for k in ScheduleStats._fields})
    # check_vma off: the replication checker cannot see through the EP
    # bodies' collectives
    y, aux = jax.shard_map(
        body, mesh=mesh, in_specs=(pspecs, in_spec),
        out_specs=(out_spec, aux_spec), check_vma=False)(routed, x)

    if "shared" in params:
        sh = params["shared"]
        xf = x.astype(jnp.float32)
        g = jnp.dot(xf, sh["w_gate"].astype(jnp.float32))
        u = jnp.dot(xf, sh["w_up"].astype(jnp.float32))
        y = y + jnp.dot((g * jax.nn.sigmoid(g)) * u,
                        sh["w_down"].astype(jnp.float32)).astype(y.dtype)
    return y, aux


def _axsize(mesh, axes) -> int:
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n

