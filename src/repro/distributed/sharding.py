"""Partition rules: parameters, optimizer state, batches, caches, activations.

Scheme (see DESIGN.md §5):
  data axis  -> batch DP + FSDP storage sharding of every weight matrix
  model axis -> EP (experts), SP/CP (sequence on the residual stream for
                transformer archs), TP-heads (ssm/hybrid mixers), KV-cache
                sequence sharding for decode
  pod axis   -> extra DP (gradient all-reduce crosses pods)

GSPMD guarantees correctness for any divisible storage sharding; these rules
choose layouts so the *propagated* compute sharding matches the scheme.  Any
axis that does not divide a dimension is dropped (replicated) — uniform
behavior for e.g. hubert's 504-way vocab.
"""
from __future__ import annotations

from typing import Dict, Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig


def dp_axes(mesh: Mesh):
    """Data-parallel axes: ('pod','data') on multi-pod, ('data',) otherwise."""
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def _fits(dim: int, mesh: Mesh, axes) -> bool:
    size = 1
    for a in (axes if isinstance(axes, tuple) else (axes,)):
        size *= mesh.shape[a]
    return dim % size == 0


def _clean(spec, shape, mesh: Mesh) -> P:
    out = []
    for dim, ax in zip(shape, tuple(spec) + (None,) * (len(shape) - len(spec))):
        out.append(ax if (ax is not None and _fits(dim, mesh, ax)) else None)
    return P(*out)


# ----------------------------------------------------------------------
# Parameters
# ----------------------------------------------------------------------
_TP_COL = {"wq", "wk", "wv", "wg", "w_gate", "w_up", "wq_a", "wkv_a",
           "wq_b", "wkv_b", "in_proj", "wr", "w_lora_a", "w_lora_b"}
_TP_ROW = {"wo", "w_down", "out_proj"}


def param_specs(params, cfg: ModelConfig, mesh: Mesh,
                mode: str = "fsdp"):
    """PartitionSpec pytree matching ``params`` (works on
    ShapeDtypeStructs).

    mode="fsdp"     — training layout: every matrix storage-sharded over
                      (data, model); gathered per layer by GSPMD (ZeRO-3).
    mode="serve_tp" — decode layout (beyond-paper §Perf): dense matrices
                      feature-split over 'model' (column for up/qkv
                      projections, row for down/output — GSPMD emits the
                      one psum per block), REPLICATED over 'data', so no
                      per-step weight gathers; expert tensors keep
                      ('model', 'data') EP+FSDP storage."""
    dp = dp_axes(mesh)
    fsdp = dp[-1]                       # 'data'
    tp = mode == "serve_tp"

    def rule(path, leaf):
        names = [getattr(p, "key", getattr(p, "name", "")) for p in path]
        name = names[-1] if names else ""
        shape = leaf.shape
        stacked = int(names[0] in ("body", "shared")) if names else 0
        core = shape[stacked:]
        if len(core) == 0 or name in ("mu", "u", "w0", "a_log", "dt_bias",
                                      "d_skip", "scale", "bias", "mask_emb") \
                or len(core) == 1:
            spec = (None,) * len(core)
        elif name == "embed":
            spec = ("model", None if tp else fsdp)
        elif name == "head":
            spec = (None, "model") if tp else (fsdp, "model")
        elif name == "router":
            spec = (None, None)
        elif len(core) == 3 and cfg.is_moe \
                and core[0] == cfg.moe.n_experts:
            # routed expert stacks: dense mats, QuantTensor payload ('q')
            # and scales ('s'), or legacy _q/_s suffix-keyed leaves — all
            # expert-leading rank 3
            if name == "s" or name.endswith("_s"):    # quant scales
                spec = ("model", None, None)
            else:                               # EP ownership + FSDP
                spec = ("model", fsdp, None)
        elif name == "conv_w":
            spec = (None, "model")
        elif tp and len(core) == 2:
            if name in _TP_ROW:
                spec = ("model", None)
            elif name in _TP_COL:
                spec = (None, "model")
            else:
                spec = (None, None)
        else:                                    # generic 2D+ matrices
            spec = (fsdp, "model") + (None,) * (len(core) - 2)
        full = (None,) * stacked + spec
        return _clean(full, shape, mesh)

    return jax.tree_util.tree_map_with_path(rule, params)


def ep_param_specs(params, cfg: ModelConfig, axis: str = "model"):
    """Expert-parallel serving layout on a 1-D EP mesh: every routed
    expert stack is split over ``axis`` on its expert dim (the layout the
    EP dispatch's shard_map reads, so no step moves weights), everything
    else is replicated.  Works on ShapeDtypeStructs."""
    E = cfg.moe.n_experts if cfg.is_moe else None

    def rule(path, leaf):
        names = [getattr(p, "key", getattr(p, "name", "")) for p in path]
        stacked = int(bool(names) and names[0] in ("body", "shared"))
        core = leaf.shape[stacked:]
        if E is not None and len(core) >= 2 and core[0] == E \
                and names[-1] != "router":
            return P(*(None,) * stacked, axis)
        return P()

    return jax.tree_util.tree_map_with_path(rule, params)


def opt_state_specs(param_spec_tree):
    """Adam moments share the param layout."""
    return {"m": param_spec_tree, "v": param_spec_tree,
            "step": P()}


# ----------------------------------------------------------------------
# Batches
# ----------------------------------------------------------------------
def batch_specs(cfg: ModelConfig, mesh: Mesh, mode: str, global_batch: int,
                microbatched: bool = False) -> Dict[str, P]:
    dp = dp_axes(mesh)
    bdp = dp if _fits(global_batch, mesh, dp) else \
        (dp[-1:] if _fits(global_batch, mesh, dp[-1]) else ())
    b = bdp if bdp else None
    seq_ax = "model" if (cfg.family not in ("ssm", "hybrid")
                         and mode != "decode") else None
    lead = (None,) if microbatched else ()
    specs = {}
    if cfg.encoder_only:
        specs["features"] = P(*lead, b, seq_ax, None)
        specs["labels"] = P(*lead, b, seq_ax)
        specs["mask"] = P(*lead, b, seq_ax)
    else:
        specs["tokens"] = P(*lead, b, seq_ax)
    if cfg.cross_attn_every:
        specs["image_embeds"] = P(*lead, b, None, None)
    return specs


# ----------------------------------------------------------------------
# Decode caches
# ----------------------------------------------------------------------
def cache_specs(cache, cfg: ModelConfig, mesh: Mesh, batch: int):
    """Seq-sharded KV caches (flash-decode); head-sharded SSM/RWKV states."""
    dp = dp_axes(mesh)
    b_ok = _fits(batch, mesh, dp)
    b = dp if b_ok else None
    # when batch can't shard (long_500k B=1), spread cache seq over data too
    seq = "model" if b_ok else (tuple(dp) + ("model",)
                                if len(dp) == 1 else ("pod", "data", "model"))

    def rule(path, leaf):
        names = [getattr(p, "key", getattr(p, "name", "")) for p in path]
        name = names[-1]
        stacked = int(names[0] == "body")
        core = leaf.shape[stacked:]
        if name in ("k", "v", "ckv", "kr"):          # (B, S, ...) kv caches
            spec = (b, seq) + (None,) * (len(core) - 2)
        elif name == "state":                        # (B, H, ...) fp32 states
            spec = (b, "model") + (None,) * (len(core) - 2)
        elif name == "conv":                         # (B, K-1, C)
            spec = (b, None, "model")
        elif name == "shift":                        # (B, 1, d)
            spec = (b, None, None)
        else:
            spec = (None,) * len(core)
        return _clean((None,) * stacked + spec, leaf.shape, mesh)

    return jax.tree_util.tree_map_with_path(rule, cache)


# ----------------------------------------------------------------------
# Activation constraint rules (consumed by distributed/ctx.py hooks)
# ----------------------------------------------------------------------
def activation_rules(cfg: ModelConfig, mesh: Mesh, mode: str,
                     global_batch: int) -> Dict[str, P]:
    dp = dp_axes(mesh)
    b = dp if _fits(global_batch, mesh, dp) else \
        (dp[-1:] if _fits(global_batch, mesh, dp[-1]) else None)
    b = tuple(b) if b else None
    if cfg.family in ("ssm", "hybrid"):
        # TP-heads: batch over data, heads/channels over model
        return {
            "residual": P(b, None, None),
            "heads4": P(b, None, "model", None),     # (B,S,H,P)
            "channels3": P(b, None, "model"),        # (B,S,C)
            "qkv": P(b, None, "model", None),
        }
    # "moe_dispatch" is the permuted (capacity, d) expert-contiguous buffer
    # every schedule policy emits (scheduling/base.py).  Its row order is a
    # data-dependent permutation of tokens, so it must never shard over
    # 'model' (the schedule is rank-local; the EP paths run under shard_map
    # and own their copies) — it rides the dp axes, matching the FSDP
    # weight-gather scheme of the grouped GEMMs.
    if mode == "decode":
        return {
            "residual": P(b, None, None),
            "qkv": P(b, None, None, None),
            "moe_dispatch": P(b, None),
        }
    # transformer train/prefill: SP/CP — sequence over model
    return {
        "residual": P(b, "model", None),
        "q_seq": P(b, "model", None, None),
        "kv_full": P(b, None, None, None),
        "moe_tokens": P(b, "model", None),
        "moe_dispatch": P(b, None),
    }
