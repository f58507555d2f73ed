"""Operations and bytes from shapes: per kernel call, and per token.

Everything is float32 (4 bytes), as the configurations serve.  A
kernel's count is what the algorithm needs for the call — the useful
rows, each touched expert's weights read once — never what a padded or
repeated schedule adds.  Sizes come from the configuration file (``c``)
and the step's counts: ``T`` tokens in the step, ``useful`` routed rows
and ``experts`` experts touched per MoE layer.
"""
from __future__ import annotations

F32 = 4


def dims(c: dict) -> dict:
    """The sizes the formulas need, from a configuration file."""
    if c["model_type"] == "deepseek_v3":
        E, f = c["n_routed_experts"], c["moe_intermediate_size"]
        shared = c["n_shared_experts"] * f
        dense = c["first_k_dense_replace"]
    else:
        E, f = c["num_local_experts"], c["intermediate_size"]
        shared, dense = 0, 0
    return dict(d=c["hidden_size"], H=c["num_attention_heads"],
                Hkv=c["num_key_value_heads"], D=c["head_dim"],
                V=c["vocab_size"], E=E, k=c["num_experts_per_tok"], f=f,
                shared=shared, f_dense=c["intermediate_size"],
                dense_layers=dense,
                moe_layers=c["num_hidden_layers"] - dense)


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
    d, H, Hkv, D = m["d"], m["H"], m["Hkv"], m["D"]
    layers = m["dense_layers"] + m["moe_layers"]
    attn = 2.0 * d * (2 * H * D + 2 * Hkv * D) + 4.0 * H * D * context
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
