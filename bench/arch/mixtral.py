"""Mixtral's family: grouped-KV attention and, in every layer, experts
of width ``intermediate_size`` under softmax gating renormalised over
the top k, with no shared experts and no dense layers."""
import costs
import model

cache_leaves = ("k", "v")

# Mixtral's keys at widths the CPU runs in seconds
TINY = {"hidden_size": 128, "intermediate_size": 256,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 32,
        "vocab_size": 512, "num_hidden_layers": 3, "num_local_experts": 4,
        "num_experts_per_tok": 2, "rope_theta": 50000,
        "rms_norm_eps": 1e-05, "tie_word_embeddings": False}


def program_config(c: dict):
    from repro.configs.base import MoEConfig
    moe = MoEConfig(
        n_experts=c["num_local_experts"], top_k=c["num_experts_per_tok"],
        d_ff_expert=c["intermediate_size"], gating="softmax",
        norm_topk=True, block_m=c["engine"]["block_m"])
    return model.gqa_config(c, moe)


def dims(c: dict) -> dict:
    return dict(d=c["hidden_size"], V=c["vocab_size"],
                E=c["num_local_experts"], k=c["num_experts_per_tok"],
                f=c["intermediate_size"], shared=0,
                f_dense=c["intermediate_size"], dense_layers=0,
                moe_layers=c["num_hidden_layers"])


attention_flops = costs.gqa_attention_flops
