"""Operations and bytes from shapes: per kernel call, and per token.

Everything is float32 (4 bytes), as the configurations serve.  A
kernel's count is what the algorithm needs for the call — the useful
rows, each touched expert's weights read once — never what a padded or
repeated schedule adds.  Sizes come from the configuration file (``c``)
and the step's counts: ``T`` tokens in the step, ``useful`` routed rows
and ``experts`` experts touched per MoE layer.  What differs between
architectures (the keys that hold the sizes, the attention's FLOPs) is
in each architecture's file (``archs.py``).
"""
from __future__ import annotations

import archs

F32 = 4


def dims(c: dict) -> dict:
    """The sizes the formulas need, from a configuration file: ``d``
    model width, ``V`` vocabulary, ``E`` routed experts of width ``f``,
    top ``k``, ``shared`` experts' summed width, ``f_dense`` the dense
    FFN's width, ``dense_layers`` and ``moe_layers``."""
    return archs.program(c["model_type"]).dims(c)


def gqa_attention_flops(c: dict, context: float) -> float:
    """One token's FLOPs in one layer of grouped-KV attention at
    ``context`` positions: Q, K, V and output projections, scores and
    values."""
    d, H, Hkv, D = (c["hidden_size"], c["num_attention_heads"],
                    c["num_key_value_heads"], c["head_dim"])
    return 2.0 * d * (2 * H * D + 2 * Hkv * D) + 4.0 * H * D * context


def expert_gemms(c: dict, useful: float, experts: float) -> tuple:
    """(flops, bytes) of one MoE layer's ``fused_gate_up`` and
    ``grouped_gemm`` calls: ``useful`` routed rows through gate, up and
    down projections; each of ``experts`` touched experts' three
    matrices read once; rows in and out."""
    m = dims(c)
    d, f = m["d"], m["f"]
    flops = 2.0 * useful * d * 3 * f
    weights = experts * 3 * d * f * F32
    acts = useful * (d + 2 * f + d) * F32   # x in, h out+in, y out
    return flops, weights + acts


def moe_dispatch(c: dict, T: float, useful: float) -> tuple:
    """(flops, bytes) of one MoE layer's ``router_topk``, ``permute``
    and ``unpermute`` calls: router scores read and top-k written;
    tokens gathered into ``useful`` expert-ordered rows; those rows
    weighted and summed back into ``T`` tokens."""
    m = dims(c)
    d, E, k = m["d"], m["E"], m["k"]
    router = (T * E + 2 * T * k) * F32, 0.0
    permute = (T * d + useful * d) * F32
    unpermute = (useful * d + useful + T * d) * F32
    flops = T * E * 4.0 + 2.0 * useful * d        # gating, weighted sum
    return flops, router[0] + permute + unpermute


def token_flops(c: dict, context: float, head: bool) -> float:
    """Model FLOPs of one token at ``context`` positions of attention
    (itself included): every matmul it needs (projections, attention
    scores and values, the dense FFN, the router, its top-k experts and
    the shared experts) and, where it yields a token, the LM head."""
    m = dims(c)
    d = m["d"]
    layers = m["dense_layers"] + m["moe_layers"]
    attn = archs.program(c["model_type"]).attention_flops(c, context)
    dense = 2.0 * 3 * d * m["f_dense"]
    moe = 2.0 * (d * m["E"] + 3 * d * (m["k"] * m["f"] + m["shared"]))
    total = (layers * attn + m["dense_layers"] * dense
             + m["moe_layers"] * moe)
    return total + (2.0 * d * m["V"] if head else 0.0)


def least_time(flops: float, nbytes: float, peak: dict) -> tuple:
    """(seconds, bound): the larger of the compute and memory times."""
    tc = flops / peak["flops_per_s"]
    tm = nbytes / peak["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")
