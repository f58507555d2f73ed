"""The trace reduction, on a small trace recorded on a TPU v5 lite
(the first events of a traced run of moonshot-chat) and on a hand-made
one whose answers are worked out below."""
import json

import pytest

import trace_reduce
from bench_kit import BENCH

KERNELS = ("fused_gate_up", "grouped_gemm", "permute", "unpermute")


def test_hand_made_trace():
    ev = {
        "modules": [("jit_step(1)", 100, 50), ("jit_step(1)", 200, 100),
                    ("jit_conv(2)", 290, 20)],
        "ops": [("%while.3 = (f32[2]) while(...)", 100, 50),
                ("%fused_gate_up.12 = f32[8] custom-call(%x)", 110, 20),
                ("%grouped_gemm.1 = f32[8] custom-call(%fused_gate_up.12)",
                 130, 10),
                ("%unpermute.4 = f32[8] custom-call(%a)", 200, 30),
                ("%permute.2 = f32[8] custom-call(%b)", 240, 5),
                ("%fusion.7 = f32[8] fusion(%c)", 250, 40)],
        "host": [("bench.traced", 0, 1000), ("bench.step", 90, 70),
                 ("bench.wait", 160, 35), ("bench.step", 195, 130),
                 ("bench.sync", 205, 95)]}
    r = trace_reduce.reduce(ev, 100, 400, kernels=KERNELS)
    # programs: [100,150] u [200,310] = 160 ns busy of a 300 ns window
    assert r["busy_s"] == pytest.approx(160e-9)
    assert r["idle_share"] == pytest.approx(1 - 160 / 300)
    assert r["kernel_s"] == pytest.approx({
        "fused_gate_up": 20e-9, "grouped_gemm": 10e-9,
        "permute": 5e-9, "unpermute": 30e-9})
    # the loop is left out of the per-op times (its body ops are there)
    assert [n for n, _ in r["device_ops"]] == [
        "fusion", "unpermute", "fused_gate_up", "grouped_gemm", "permute"]
    # gaps: [150,200] (middle 175: inside bench.wait) and [310,400]
    # (middle 355: only bench.traced holds it)
    assert r["idle_gaps"] == [["bench.traced", pytest.approx(90e-9)],
                              ["bench.wait", pytest.approx(50e-9)]]


def test_recorded_trace():
    ev = json.loads((BENCH / "data" / "trace_small.json").read_text())
    lo = min(s for _, s, _ in ev["modules"])
    hi = max(s + d for _, s, d in ev["modules"])
    r = trace_reduce.reduce(ev, lo, hi, kernels=KERNELS)
    # the programs of one process run one at a time: busy is their sum
    assert r["busy_s"] == pytest.approx(
        sum(d for _, _, d in ev["modules"]) / 1e9)
    assert 0.0 < r["idle_share"] < 1.0
    gaps = sum(g for _, g in r["idle_gaps"])
    assert gaps == pytest.approx(r["window_s"] - r["busy_s"])
    assert {n for n, _ in r["idle_gaps"]} <= {
        "bench.traced", "bench.wait", "bench.step", "bench.submit",
        "bench.schedule", "bench.forward", "bench.sync", "host idle"}
    names = {trace_reduce.op_family(n) for n, _, _ in ev["ops"]}
    assert "copy" in names


def test_op_family():
    f = trace_reduce.op_family
    assert f("%fused_gate_up.12 = f32[8320,1408] custom-call(%x)") == \
        "fused_gate_up"
    assert f("%paged_decode_attention.14 = f32[8,16] custom-call(%a)") == \
        "paged_decode_attention"
    assert f("jit_paged_step(17899797691468364454)") == "jit_paged_step"
    assert f("%copy-start.42 = (s32[2,1]) copy-start(%y)") == "copy-start"
