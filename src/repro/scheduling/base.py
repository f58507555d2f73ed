"""Schedule-policy layer: types, telemetry, and the policy registry.

The paper builds ONE fixed-tile block schedule (Algorithm 1).  Production
traffic is not uniform — under Zipfian routing skew the fixed-``block_m``
layout pads hard (paper §4.7, our skew_sensitivity benchmark) — so schedule
construction is a *pluggable policy*:

* ``fixed``            — the paper's tile-aligned layout (scheduling/fixed.py)
* ``capacity_factor``  — bounded per-expert capacity, GShard-style overflow
                         drops with residual pass-through (scheduling/capacity.py)
* ``dynamic``          — the paper's proposed future work: adaptive per-expert
                         block sizing + greedy packing (scheduling/dynamic.py)

Every policy is a function ``(indices, n_experts, block_m, **kw) ->
BlockSchedule`` built from on-device jnp primitives only (no host sync —
the TPU scalar-prefetch property of core/schedule.py is preserved), and all
policies emit the same ``BlockSchedule`` contract so every consumer (Pallas
kernels, the XLA scan, the EP paths) works with any policy unchanged:

  - uniform physical block size ``block_m`` (policies may *shrink* it, e.g.
    ``dynamic`` schedules sub-tiles);
  - every block is owned by exactly one expert (``block_expert``), inactive
    blocks carry only padding (``block_active``);
  - ``src_tok == -1`` marks padding rows; ``pos`` maps each expanded token
    (t, j) to its padded row (dropped assignments point at a zeroed row).
"""
from __future__ import annotations

from typing import Callable, Dict, NamedTuple, Optional

import jax.numpy as jnp


def round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


class BlockSchedule(NamedTuple):
    """Everything the dispatch pipeline needs, all device arrays.

    With T = tokens, k = top_k, E = experts, M = the policy's physical
    block size: capacity is a static, policy-dependent row budget and
    num_blocks = capacity // M.
    """

    counts: jnp.ndarray          # (E,)  int32 tokens routed to each expert (pre-drop)
    group_offsets: jnp.ndarray   # (E+1,) int32 segment starts; for the
                                 # ``dynamic`` policy these are in packing
                                 # order (use ``seg_start`` per expert)
    src_tok: jnp.ndarray         # (capacity,) int32 source token row, -1 = padding
    pos: jnp.ndarray             # (T, k) int32 padded row of expanded token (t, j)
    block_expert: jnp.ndarray    # (num_blocks,) int32 owning expert (clamped)
    block_active: jnp.ndarray    # (num_blocks,) int32 1 = block has real rows
    capacity: int                # static
    block_m: int                 # static physical block size
    seg_start: Optional[jnp.ndarray] = None   # (E,) int32 per-expert base row
                                              # (None = group_offsets[:-1])


class ScheduleStats(NamedTuple):
    """Per-schedule telemetry (all 0-d jnp arrays — traced-safe).

    Emitted by every policy; consumed by benchmarks/skew_sensitivity.py and
    analysis/report.py to compare policies head-to-head.
    """

    useful_rows: jnp.ndarray     # kept (non-dropped) expanded tokens
    dropped_rows: jnp.ndarray    # assignments dropped by bounded capacity
    padded_rows: jnp.ndarray     # rows covered by ACTIVE blocks (compute cost)
    pad_waste: jnp.ndarray       # padded_rows / useful_rows
    drop_fraction: jnp.ndarray   # dropped / (T*k)
    top1_share: jnp.ndarray      # heaviest expert's share of raw routing
    n_blocks_active: jnp.ndarray
    occupancy: jnp.ndarray       # useful_rows / padded_rows
    grid_rows: jnp.ndarray       # static capacity: rows the kernels' grid walks


def schedule_stats(sched: BlockSchedule) -> ScheduleStats:
    """Telemetry from any policy's schedule (pure jnp, no host sync)."""
    n_assign = jnp.int32(sched.pos.size)
    useful = jnp.sum((sched.src_tok >= 0).astype(jnp.int32))
    dropped = n_assign - useful
    n_active = jnp.sum(sched.block_active.astype(jnp.int32))
    padded = n_active * sched.block_m
    total = jnp.sum(sched.counts)
    f32 = jnp.float32
    safe = lambda a, b: a.astype(f32) / jnp.maximum(b, 1).astype(f32)
    return ScheduleStats(
        useful_rows=useful,
        dropped_rows=dropped,
        padded_rows=padded,
        pad_waste=safe(padded, useful),
        drop_fraction=safe(dropped, n_assign),
        top1_share=safe(jnp.max(sched.counts), total),
        n_blocks_active=n_active,
        occupancy=safe(useful, padded),
        grid_rows=jnp.int32(sched.capacity),
    )


# The canonical head-to-head sweep — (policy name, build kwargs) — shared
# by benchmarks/skew_sensitivity.py, examples/skew_study.py, and the
# invariants tests so they always compare the same policy set.
DEFAULT_POLICY_SWEEP = (
    ("fixed", {}),
    ("capacity_factor", {"capacity_factor": 1.25}),
    ("dynamic", {}),
)


# ----------------------------------------------------------------------
# Policy registry
# ----------------------------------------------------------------------
PolicyFn = Callable[..., BlockSchedule]

_POLICIES: Dict[str, PolicyFn] = {}
_POLICY_CONFIG_FIELDS: Dict[str, tuple] = {}


def register_policy(name: str, *, config_fields: tuple = ()
                    ) -> Callable[[PolicyFn], PolicyFn]:
    """Register a schedule policy.  ``config_fields`` names the dispatch-
    config fields this policy consumes as build kwargs (e.g. the
    ``capacity_factor`` policy reads ``cfg.capacity_factor``) — consumers
    call ``policy_config_kwargs`` instead of hard-coding per-policy
    branches."""
    def deco(fn: PolicyFn) -> PolicyFn:
        _POLICIES[name] = fn
        _POLICY_CONFIG_FIELDS[name] = tuple(config_fields)
        return fn
    return deco


def policy_config_kwargs(policy: str, cfg) -> dict:
    """The registered policy's build kwargs, read off any config object
    carrying the fields the policy declared at registration."""
    get_policy(policy)                       # uniform unknown-policy error
    return {f: getattr(cfg, f) for f in _POLICY_CONFIG_FIELDS[policy]}


def get_policy(name: str) -> PolicyFn:
    try:
        return _POLICIES[name]
    except KeyError:
        raise ValueError(f"unknown schedule policy {name!r}; "
                         f"available: {available_policies()}") from None


def available_policies():
    return sorted(_POLICIES)


def build_schedule(indices: jnp.ndarray, n_experts: int, block_m: int,
                   policy: str = "fixed", **kwargs) -> BlockSchedule:
    """Construct a block schedule under the named policy.

    indices: (T, k) int32 expert assignment per token.  Defaults to the
    paper's ``fixed`` policy, so existing positional call sites
    (``build_schedule(idx, E, M)``) keep their exact behavior.
    """
    return get_policy(policy)(indices, n_experts, block_m, **kwargs)
