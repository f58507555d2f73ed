"""The reference of DeepSeek-V3's family as the program runs it (see
``arch/deepseek_v3.py``): grouped-KV attention; leading dense layers;
``scoring_func`` scores, the top ``num_experts_per_tok`` by score,
renormalised where ``norm_topk_prob`` says so and scaled by
``routed_scaling_factor``; the shared experts added."""
import reference as R

KEYS = ("num_attention_heads", "num_key_value_heads", "head_dim",
        "rope_theta", "rms_norm_eps", "num_experts_per_tok",
        "norm_topk_prob", "routed_scaling_factor", "scoring_func")

# grouped-KV attention and the mixture of experts: the shared decoder
forward = R._decoder
