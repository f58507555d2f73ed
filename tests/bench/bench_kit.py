"""Helpers of the benchmark's CPU tests: a cell at a size the CPU runs."""
import json
import pathlib
import types

BENCH = pathlib.Path(__file__).resolve().parents[2] / "bench"

TINY = {"hidden_size": 128, "intermediate_size": 256,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 32,
        "vocab_size": 512}
TINY_BY_TYPE = {
    "deepseek_v3": {"moe_intermediate_size": 64, "n_routed_experts": 8,
                    "num_experts_per_tok": 2, "num_key_value_heads": 4},
    "mixtral": {"num_local_experts": 4, "num_experts_per_tok": 2}}


class CpuDevice:
    platform, device_kind = "cpu", "cpu"

    def memory_stats(self):
        return {"peak_bytes_in_use": 1}


CPU_PEAK = {"flops_per_s": 1e12, "hbm_bytes_per_s": 1e11, "hbm_bytes": 1e10}


# keys of the other MoE family the harness reads (Mixtral's naming):
# softmax gating renormalised over the top k, no shared experts
DEEPSEEK_ONLY = ("n_routed_experts", "moe_intermediate_size",
                 "n_shared_experts", "first_k_dense_replace",
                 "scoring_func", "routed_scaling_factor", "norm_topk_prob")


def tiny_cell(tmp_path, model_type="deepseek_v3", *, rate=30.0,
              lead_in=4.0):
    """The cell moonshot-chat at a size the CPU runs in seconds: its
    configuration file with small widths (with ``model_type`` "mixtral",
    turned into the Mixtral family's keys), two slots, short prompts and
    answers, a short lead-in, and the limits of ``correct`` unchanged.
    The rate is past what the two slots serve, so both are busy in
    nearly every step.  It reports the manifest's end-to-end metrics for
    the cell."""
    import run
    found = run.cell_of(run.manifest(), "moonshot-chat")
    c = json.loads(found["config_file"].read_text())
    if model_type == "mixtral":
        for k in DEEPSEEK_ONLY:
            c.pop(k)
        c["model_type"] = "mixtral"
    c.update(TINY, **TINY_BY_TYPE[model_type])
    c["engine"].update(slots=2, capacity=96, prefill_chunk=2, block_m=8)
    c["correct"]["sample"] = {"min_tokens": 20, "max_requests": 8,
                              "min_kv_rows": 16}
    mix = json.loads(found["traffic_file"].read_text())
    mix["arrivals"] = {"kind": "poisson", "rate_per_s": rate,
                       "lead_in_s": lead_in}
    mix["prompt_tokens"].update(median=24, min=18, max=40)
    mix["output_tokens"].update(median=8, min=6, max=16)
    (tmp_path / "cfg.json").write_text(json.dumps(c))
    (tmp_path / "mix.json").write_text(json.dumps(mix))
    return dict(found, per_layer=[], config_file=tmp_path / "cfg.json",
                traffic_file=tmp_path / "mix.json")


def run_args(seed, seconds=3.0, trace=0):
    return types.SimpleNamespace(seed=seed, seconds=seconds, trace=trace)
