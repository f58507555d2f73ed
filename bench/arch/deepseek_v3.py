"""DeepSeek-V3's family as the program runs it (Moonlight-16B-A3B's
``model_type``): grouped-KV attention in place of MLA (the
configuration's ``assumed``), leading dense layers, routed experts of
width ``moe_intermediate_size`` scored by ``scoring_func``, and shared
experts run as one SwiGLU MLP of their summed width."""
import costs
import model

cache_leaves = ("k", "v")

# Moonlight's keys at widths the CPU runs in seconds
TINY = {"hidden_size": 128, "intermediate_size": 256,
        "num_attention_heads": 4, "num_key_value_heads": 4, "head_dim": 32,
        "vocab_size": 512, "num_hidden_layers": 3,
        "first_k_dense_replace": 1, "n_routed_experts": 8,
        "num_experts_per_tok": 2, "moe_intermediate_size": 64,
        "n_shared_experts": 2, "scoring_func": "sigmoid",
        "norm_topk_prob": True, "routed_scaling_factor": 2.446,
        "rope_theta": 50000, "rms_norm_eps": 1e-05,
        "tie_word_embeddings": False}


def program_config(c: dict):
    from repro.configs.base import MoEConfig
    moe = MoEConfig(
        n_experts=c["n_routed_experts"], top_k=c["num_experts_per_tok"],
        d_ff_expert=c["moe_intermediate_size"],
        n_shared_experts=c["n_shared_experts"],
        gating=c["scoring_func"], norm_topk=c["norm_topk_prob"],
        routed_scale=c["routed_scaling_factor"],
        first_dense_layers=c["first_k_dense_replace"],
        d_ff_dense=c["intermediate_size"], block_m=c["engine"]["block_m"])
    return model.gqa_config(c, moe)


def dims(c: dict) -> dict:
    f, dense = c["moe_intermediate_size"], c["first_k_dense_replace"]
    return dict(d=c["hidden_size"], V=c["vocab_size"],
                E=c["n_routed_experts"], k=c["num_experts_per_tok"], f=f,
                shared=c["n_shared_experts"] * f,
                f_dense=c["intermediate_size"], dense_layers=dense,
                moe_layers=c["num_hidden_layers"] - dense)


attention_flops = costs.gqa_attention_flops
