"""What the harness computes for each architecture, against a record
made before each architecture's code moved into files of its own
(``bench/arch/``, ``bench/ref/``): the program's config, the reference's
hidden states, cache rows and router margins (full precision and the
control), the gaps of served tokens, and the costs, for the tiny
``deepseek_v3`` and ``mixtral`` cells, bit for bit."""
import hashlib
import json
import pathlib

import numpy as np
import pytest

import costs
import model
import reference
from bench_kit import tiny_cell

GOLDEN = pathlib.Path(__file__).resolve().parent / "data" / "arch_golden.json"
WEIGHT_SEED = 2**33 + 5


def digest(a) -> str:
    a = np.ascontiguousarray(np.asarray(a))
    return f"{a.dtype}{a.shape}:" + hashlib.sha256(a.tobytes()).hexdigest()


def record(tmp_path, model_type) -> dict:
    """Every number the golden file holds, for one architecture."""
    c = json.loads(tiny_cell(tmp_path, model_type)["config_file"]
                   .read_text())
    cfg = model.model_config(c)
    params = model.make_weights(cfg, WEIGHT_SEED)
    rng = np.random.default_rng(7)
    prompt = rng.integers(0, c["vocab_size"], 37)
    served = rng.integers(0, c["vocab_size"], 9)
    seq = reference.Sequence(c, params, prompt, served, control=True)
    return {
        "model_config": repr(cfg),
        "h": digest(seq.h), "h_ctl": digest(seq.h_ctl),
        "margin": digest(seq.margin),
        "kv": [[digest(a) for a in layer] for layer in seq.kv],
        "kv_ctl": [[digest(a) for a in layer] for layer in seq.kv_ctl],
        "served_gaps": [repr(float(g)) for g in seq.served_gaps()],
        "control_gaps": [repr(float(g)) for g in seq.control_gaps()],
        "costs": [repr(x) for x in (
            costs.token_flops(c, 17, False), costs.token_flops(c, 1, True),
            *costs.expert_gemms(c, 12, 3), *costs.moe_dispatch(c, 6, 12))],
    }


@pytest.mark.parametrize("model_type", ["deepseek_v3", "mixtral"])
def test_moved_architecture_reproduces_its_record(tmp_path, model_type):
    want = json.loads(GOLDEN.read_text())[model_type]
    got = record(tmp_path, model_type)
    for key in want:
        assert got[key] == want[key], key
