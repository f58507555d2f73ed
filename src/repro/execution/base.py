"""Plan/execute split for MoE dispatch: `DispatchPlan` + executor registry.

The paper's pipeline (router -> schedule -> permute -> grouped GEMMs ->
combine) used to live as one monolithic function with string-compare
backend branches.  It is now two phases with one contract (DESIGN.md §6):

* **Plan** — `plan_dispatch(x, w_router, cfg)` runs the router, builds the
  configured `BlockSchedule`, scatters the combine-scale rows, and collects
  aux/telemetry.  Everything routing-dependent is computed exactly once per
  batch and is backend-independent: any executor can consume any plan.
* **Execute** — an `Executor` turns a plan into the layer output, either
  through the phase methods (`permute` / `expert_ffn` / `unpermute` — the
  granularity the EP paths compose) or the whole-plan `run` (backends such
  as `dense` that have no permuted layout at all).

Backends register under a name (`pallas`, `xla`, `dense` ship built-in);
``MoEDispatchConfig.executor`` selects one.  Adding a backend — a future
ragged-dot executor, a CPU-offload executor — is one registered module, not
another ``elif`` in core code.
"""
from __future__ import annotations

from typing import Callable, Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.distributed.ctx import constrain
from repro.scheduling import (BlockSchedule, build_schedule,
                              policy_config_kwargs, schedule_stats)


class DispatchPlan(NamedTuple):
    """Everything per-batch and routing-dependent, built once by
    `plan_dispatch` and consumable by every executor.

    ``schedule`` / ``combine_scale`` are None when the plan was built
    without a schedule (``with_schedule=False`` — the EP paths derive their
    own rank-local layouts from ``indices``) or when the selected executor
    declares ``needs_schedule = False`` (the dense oracle)."""

    weights: jnp.ndarray                   # (T, k) f32 combine weights
    indices: jnp.ndarray                   # (T, k) i32 expert assignment
    logits: jnp.ndarray                    # (T, E) f32 router logits
    schedule: Optional[BlockSchedule]      # the configured policy's layout
    combine_scale: Optional[jnp.ndarray]   # (capacity,) f32 epilogue rows
    aux: dict                              # lb/z losses (+ sched/* stats)


def router_aux_losses(logits: jnp.ndarray, indices: jnp.ndarray, cfg):
    """Load-balance + router-z losses (training substrate; the paper is
    inference-only so these sit outside its measured pipeline)."""
    probs = jax.nn.softmax(logits, axis=-1)
    E = cfg.n_experts
    frac = jnp.mean(
        jax.nn.one_hot(indices, E, dtype=jnp.float32), axis=(0, 1))
    mean_prob = jnp.mean(probs, axis=0)
    lb = E * jnp.sum(frac * mean_prob)
    z = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)
    return {"lb_loss": lb, "router_z": z}


def combine_scale_rows(sched: BlockSchedule, weights: jnp.ndarray):
    """Scatter the (T, k) combine weights onto padded rows for the fused
    down-projection epilogue. Padding rows get 0."""
    scale = jnp.zeros((sched.capacity,), jnp.float32)
    return scale.at[sched.pos.reshape(-1)].set(
        weights.reshape(-1).astype(jnp.float32), mode="drop")


def plan_schedule(indices: jnp.ndarray, cfg) -> BlockSchedule:
    """The configured policy's schedule for this batch's routing.  Each
    policy declares which config fields it consumes (scheduling/base.py).

    Under ``autotune=True`` a policy consuming ``block_m_min`` (the
    dynamic policy's sub-block floor) gets it overridden by a swept
    ``sub_block`` tune-cache record for this routing shape, when one
    exists — the same trace-time consult idiom as the kernel tiles
    (repro.tuning, DESIGN.md §12)."""
    kw = policy_config_kwargs(cfg.schedule_policy, cfg)
    if getattr(cfg, "autotune", False) and "block_m_min" in kw:
        from repro.tuning import lookup_block_sizes
        rec = lookup_block_sizes(
            "sub_block", M=int(indices.shape[0]) * cfg.top_k,
            K=cfg.block_m, N=0, E=cfg.n_experts,
            executor=cfg.executor)
        if rec is not None and "block_m_min" in rec:
            kw["block_m_min"] = int(rec["block_m_min"])
    return build_schedule(
        indices, cfg.n_experts, cfg.block_m, policy=cfg.schedule_policy,
        **kw)


# ----------------------------------------------------------------------
# Executor protocol + registry
# ----------------------------------------------------------------------
class Executor:
    """Backend contract for the grouped expert compute.

    Phase methods (`permute` / `expert_ffn` / `unpermute`) operate on a
    `BlockSchedule` and are what the EP layer composes rank-locally; the
    whole-plan `run` is what single-device dispatch calls and is the only
    entry a schedule-free backend (dense) must provide.  ``w`` is always
    the expert-weight mapping {"w_gate", "w_up", "w_down"} of (E, K, N)
    arrays or scheme-tagged QuantTensors (repro.quantization).

    Quantization capability is part of the contract (DESIGN.md §8):
    ``supports_scheme`` declares which registered schemes the backend can
    consume, and ``prepare_weights`` is the hook between the plan and the
    grouped compute — the base implementation materializes QuantTensors
    to dense stacks (correct for any backend, e.g. the dense oracle); the
    in-scan backends (xla, pallas) override it to pass compressed weights
    through and dequantize each gathered block inside the grouped-GEMM
    scan instead.
    """

    name: str = "?"
    needs_schedule: bool = True       # plan carries a BlockSchedule

    # -- quantization capability --------------------------------------
    def supports_scheme(self, scheme: str) -> bool:
        """Whether this backend can consume expert weights quantized
        under ``scheme``.  The default covers every registered scheme via
        the materializing ``prepare_weights``; a backend with a narrower
        contract (a future fused-int8-only kernel) overrides this."""
        from repro.quantization import available_schemes
        return scheme in available_schemes()

    def prepare_weights(self, w: dict, cfg) -> dict:
        """Adapt the expert-weight mapping to this backend, called once
        per plan execution.  Default: materialize QuantTensors to dense
        (E, K, N) stacks.  In-scan backends override to the identity and
        dequantize per gathered block instead."""
        from repro.quantization import QuantTensor
        return {k: (v.materialize() if isinstance(v, QuantTensor) else v)
                for k, v in w.items()}

    # -- routing ------------------------------------------------------
    def route(self, logits: jnp.ndarray, cfg):
        """(T, E) f32 logits -> (weights (T, k) f32, indices (T, k) i32)."""
        from repro.kernels import ref
        return ref.router_ref(logits, cfg.top_k, gating=cfg.gating,
                              norm_topk=cfg.norm_topk,
                              routed_scale=cfg.routed_scale)

    # -- phases -------------------------------------------------------
    def permute(self, x, sched: BlockSchedule, cfg):
        raise NotImplementedError(
            f"executor {self.name!r} has no phase-level permute")

    def expert_ffn(self, xp, w: dict, sched: BlockSchedule, cfg,
                   row_scale=None):
        """Grouped gate+up activation and down projection on a schedule."""
        raise NotImplementedError(
            f"executor {self.name!r} has no phase-level expert_ffn")

    def unpermute(self, y, sched: BlockSchedule, weights, cfg):
        raise NotImplementedError(
            f"executor {self.name!r} has no phase-level unpermute")

    # -- whole plan ---------------------------------------------------
    def run(self, x, w: dict, plan: DispatchPlan, cfg):
        """x: (T, d) -> y: (T, d) under the plan's routing + schedule."""
        sched = plan.schedule
        if sched is None:
            raise ValueError(
                f"executor {self.name!r} needs a schedule, but this plan "
                "carries none (built with with_schedule=False or by a "
                "needs_schedule=False executor) — rebuild it with "
                "plan_dispatch(..., with_schedule=True)")
        w = self.prepare_weights(w, cfg)
        xp = constrain("moe_dispatch", self.permute(x, sched, cfg))
        scale = plan.combine_scale if cfg.fold_combine else None
        y = self.expert_ffn(xp, w, sched, cfg, row_scale=scale)
        return self.unpermute(
            y, sched, None if cfg.fold_combine else plan.weights, cfg)


_EXECUTORS: Dict[str, Executor] = {}


def register_executor(name: str) -> Callable[[type], type]:
    """Class decorator: instantiate and register an Executor under `name`."""
    def deco(cls: type) -> type:
        cls.name = name
        _EXECUTORS[name] = cls()
        return cls
    return deco


def get_executor(name) -> Executor:
    if isinstance(name, Executor):
        return name
    try:
        return _EXECUTORS[name]
    except KeyError:
        raise ValueError(f"unknown executor {name!r}; "
                         f"available: {available_executors()}") from None


def available_executors():
    return sorted(_EXECUTORS)


# ----------------------------------------------------------------------
# The two API entry points
# ----------------------------------------------------------------------
def plan_dispatch(x: jnp.ndarray, w_router: jnp.ndarray, cfg, *,
                  with_schedule: Optional[bool] = None) -> DispatchPlan:
    """Phase 1: route + schedule + combine rows + aux, once per batch.

    x: (T, d).  The router projection stays XLA (near-optimal small-N GEMM,
    as in the paper); gating/top-k selection is the executor's (the pallas
    executor runs its fused router kernel).  ``with_schedule`` overrides the
    executor's ``needs_schedule`` — the EP paths pass False and derive
    rank-local layouts from ``plan.indices`` instead.
    """
    ex = get_executor(cfg.executor)
    logits = jnp.dot(x.astype(jnp.float32), w_router.astype(jnp.float32))
    weights, indices = ex.route(logits, cfg)
    aux = router_aux_losses(logits, indices, cfg)

    build = ex.needs_schedule if with_schedule is None else with_schedule
    sched = combine = None
    if build:
        sched = plan_schedule(indices, cfg)
        combine = combine_scale_rows(sched, weights) \
            if cfg.fold_combine else None
        if cfg.emit_stats:
            aux.update({f"sched/{k}": v for k, v
                        in schedule_stats(sched)._asdict().items()})
    return DispatchPlan(weights=weights, indices=indices, logits=logits,
                        schedule=sched, combine_scale=combine, aux=aux)


def execute(plan: DispatchPlan, x: jnp.ndarray, w: dict, cfg,
            executor=None) -> jnp.ndarray:
    """Phase 2: run a plan through a backend.  ``executor`` (name or
    instance) defaults to ``cfg.executor`` — pass another registered name to
    re-execute the SAME plan on a different backend (tested parity)."""
    ex = get_executor(cfg.executor if executor is None else executor)
    return ex.run(x, w, plan, cfg)
