"""A whole run on the CPU at a tiny size, with the look for a chip
skipped: sound, it is correct; with the timed path broken underneath in
each way a one-chip serving cell can break, ``correct`` comes out false.

The faults, planted in the engine's jitted step:

- ``state_unchanged``: the step returns the KV pool it was given, so
  nothing the step computes is kept;
- ``half_batch``: the second half of the step's rows get the first
  half's results in place of their own;
- ``token_altered``: every token the step produces is changed.

(The exchange between chips does not exist on one chip.)
"""
import pytest

import repro.serve.engine as engine_mod
import run
from bench_kit import CPU_PEAK, CpuDevice, run_args, tiny_cell


def broken(fault):
    make = engine_mod.make_paged_step

    def make_broken(*a, **kw):
        step = make(*a, **kw)

        def f(params, pools, batch, pos, *rest):
            tok, hit, new_pools, aux = step(params, pools, batch, pos, *rest)
            if fault == "state_unchanged":
                new_pools = pools
            elif fault == "half_batch":
                h = tok.shape[0] // 2
                if h:
                    tok = tok.at[tok.shape[0] - h:].set(tok[:h])
            elif fault == "token_altered":
                tok = (tok + 1) % 512
            return tok, hit, new_pools, aux
        return f
    return make_broken


@pytest.mark.parametrize("model_type", ["deepseek_v3", "mixtral"])
@pytest.mark.parametrize("fault", [None, "state_unchanged", "half_batch",
                                   "token_altered"])
def test_fault_makes_the_run_incorrect(tmp_path, monkeypatch, model_type,
                                       fault):
    if fault:
        monkeypatch.setattr(engine_mod, "make_paged_step", broken(fault))
    found = tiny_cell(tmp_path, model_type)
    line = run.execute(found, run_args(seed=2**33 + 21), [CpuDevice()],
                       CPU_PEAK)
    compared = {x["name"]: x for x in line["compared"]}
    assert compared["checked_tokens"]["value"] >= 20
    assert line["correct"] is (fault is None), compared
