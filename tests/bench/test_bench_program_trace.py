"""The device's idle time charged to the program's spans
(``bench/program_trace.py``): on a hand-made trace whose split is
worked out below, on a small trace recorded on a TPU v5 lite (the first
events of a traced run of moonshot-chat, the engine's ``serve/`` spans
among them) and on a profile recorded here; and the trace reduction it
sits beside, which reads the older recorded trace as it always has."""
import json

import pytest

import program_trace
import trace_reduce
from bench_kit import BENCH

# device programs [100, 300] and [500, 800] in a window [0, 1000]: idle
# [0, 100], [300, 500] and [800, 1000], 500 ns in all
HAND = {
    "modules": [("jit_c(1)", -50, 20), ("jit_a(2)", 100, 200),
                ("jit_b(3)", 500, 300)],
    "ops": [],
    "host": [("bench.traced", 0, 1000)],
    "program": [
        ("serve/poll", -100, 140),        # began before the window
        ("serve/step", -90, 100),
        ("serve/poll", 50, 830),
        ("serve/step", 60, 800),
        ("serve/assemble", 60, 30),
        ("serve/inputs", 70, 20),
        ("serve/forward", 90, 320),       # crosses into the busy time
        ("serve/host_sync", 410, 60),
        ("serve/postprocess", 470, 20),
        ("serve/stream", 475, 10),
        ("serve/poll", 890, 300),         # runs past the window
        ("serve/step", 900, 150)]}


def test_hand_made_split():
    r = program_trace.split(HAND, 0, 1000)
    # [0, 100]: step 0-10 (the step begun before the window), poll
    # 10-40, outside 40-50, poll 50-60, assemble 60-70, inputs 70-90,
    # forward 90-100.  [300, 500]: forward 300-410, host_sync 410-470,
    # postprocess 470-475 and 485-490, stream 475-485, the step's own
    # time 490-500.  [800, 1000]: step 800-860, poll 860-880, outside
    # 880-890, the second poll 890-900, the second step 900-1000.
    ns = {"serve/step": 180, "serve/forward": 120, "serve/poll": 70,
          "serve/host_sync": 60, "outside": 20, "serve/inputs": 20,
          "serve/assemble": 10, "serve/postprocess": 10,
          "serve/stream": 10}
    assert r["idle_s"] == pytest.approx({k: v / 1e9 for k, v in ns.items()})
    assert sum(r["idle_s"].values()) == pytest.approx(500e-9)
    # the steps that begin in the window: at 60 and at 900
    assert r["steps"] == 2
    ms = program_trace.per_step(r)
    assert ms == pytest.approx({"host": 230e-6 / 2, "dispatch": 180e-6 / 2,
                                "frontend": 90e-6 / 2})
    table = program_trace.table(r)
    assert table.splitlines()[1].split()[0] == "serve/step"
    assert program_trace.window(HAND) == (0, 1000)


def test_a_gap_with_no_span_is_outside_and_no_step_reads_nothing():
    ev = {"modules": [("jit_a(1)", 10, 10)], "program": [], "host": []}
    r = program_trace.split(ev, 0, 40)
    assert r == {"idle_s": {"outside": pytest.approx(30e-9)}, "steps": 0}
    assert program_trace.per_step(r) is None
    assert program_trace.window(ev) == (10, 20)


def test_recorded_trace_with_program_spans():
    """The three groups times the steps are the window's idle time as
    ``trace_reduce.reduce`` reads it, exactly."""
    ev = json.loads((BENCH / "data" / "trace_program_small.json")
                    .read_text())
    lo = min(s for _, s, _ in ev["modules"])
    hi = max(s + d for _, s, d in ev["modules"])
    red = trace_reduce.reduce(ev, lo, hi)
    r = program_trace.split(ev, lo, hi)
    assert r["steps"] > 0
    ms = program_trace.per_step(r)
    assert sum(ms.values()) * r["steps"] / 1e3 == pytest.approx(
        red["window_s"] - red["busy_s"], rel=1e-9)
    assert all(v >= 0 for v in ms.values())
    names = {n for n, _, _ in ev["program"]}
    assert {"serve/poll", "serve/step", "serve/assemble", "serve/inputs",
            "serve/forward", "serve/host_sync", "serve/postprocess"} \
        <= names
    assert set(r["idle_s"]) <= names | {"outside"}


def test_reduce_reads_the_older_recorded_trace_as_before():
    ev = json.loads((BENCH / "data" / "trace_small.json").read_text())
    lo = min(s for _, s, _ in ev["modules"])
    hi = max(s + d for _, s, d in ev["modules"])
    r = trace_reduce.reduce(ev, lo, hi)
    assert r["window_s"] == pytest.approx(1.502099802)
    assert r["busy_s"] == pytest.approx(1.335911627)
    assert r["idle_share"] == pytest.approx(0.11063723913599188)
    assert r["idle_gaps"] == [["bench.traced", pytest.approx(0.166188175)]]
    assert r["device_ops"][:2] == [["copy", pytest.approx(0.001109957)],
                                   ["reshape", pytest.approx(0.001025019)]]
    # the older trace has no program spans: nothing to split by
    assert program_trace.split(dict(ev, program=[]), lo, hi)["steps"] == 0


def test_events_read_the_programs_spans_from_a_profile(tmp_path):
    """A profile recorded here (CPU, so it holds no TPU programs) gives
    the program's ``serve/`` spans under ``program`` and leaves the
    benchmark's ``bench.*`` spans under ``host``."""
    import jax
    import jax.numpy as jnp

    from repro.obs import SpanTracer
    tracer = SpanTracer()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("bench.traced"):
            with tracer.step("serve/step", 0, active=1):
                with tracer.span("serve/forward", tokens=3):
                    jnp.arange(3).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    ev = program_trace.events(trace_reduce.find_xplane(tmp_path))
    assert sorted(n for n, _, _ in ev["program"]) == ["serve/forward",
                                                      "serve/step"]
    assert [n for n, _, _ in ev["host"]] == ["bench.traced"]
    assert ev["modules"] == []
    (step,) = [e for e in ev["program"] if e[0] == "serve/step"]
    (fwd,) = [e for e in ev["program"] if e[0] == "serve/forward"]
    assert step[1] <= fwd[1] and fwd[1] + fwd[2] <= step[1] + step[2]
