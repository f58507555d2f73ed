"""The reference of Mixtral's family: grouped-KV attention; softmax
scores, the top ``num_experts_per_tok`` renormalised over the chosen
(``_moe``'s defaults, since the configuration names no scoring); no
shared experts."""
import reference as R

KEYS = ("num_attention_heads", "num_key_value_heads", "head_dim",
        "rope_theta", "rms_norm_eps", "num_experts_per_tok")

# grouped-KV attention and the mixture of experts: the shared decoder
forward = R._decoder
