"""Helpers of the benchmark's CPU tests: a cell at a size the CPU runs."""
import json
import pathlib
import types

import archs

BENCH = pathlib.Path(__file__).resolve().parents[2] / "bench"

# every architecture that has its files (``bench/archs.py``): each one's
# CPU tests run at the small widths its file gives
ARCHS = archs.known()
# the blocks of a configuration file that are the harness's own, not
# the architecture's
HARNESS_KEYS = ("name", "precision", "as_run", "engine", "correct")


class CpuDevice:
    platform, device_kind = "cpu", "cpu"

    def memory_stats(self):
        return {"peak_bytes_in_use": 1}


CPU_PEAK = {"flops_per_s": 1e12, "hbm_bytes_per_s": 1e11, "hbm_bytes": 1e10}


def tiny_cell(tmp_path, model_type="deepseek_v3", *, rate=30.0,
              lead_in=4.0):
    """The cell moonshot-chat at a size the CPU runs in seconds, for the
    architecture ``model_type``: the harness's blocks of the cell's
    configuration file, with two slots and the limits of ``correct``
    unchanged, and the architecture's keys at the small widths of its
    file (``TINY``); short prompts and answers, a short lead-in.  The
    rate is past what the two slots serve, so both are busy in nearly
    every step.  It reports the manifest's end-to-end metrics for the
    cell."""
    import run
    found = run.cell_of(run.manifest(), "moonshot-chat")
    full = json.loads(found["config_file"].read_text())
    c = {k: full[k] for k in HARNESS_KEYS}
    c.update(archs.program(model_type).TINY, model_type=model_type)
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
