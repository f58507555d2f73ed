"""Observability layer (repro.obs): sink units, Chrome-trace validity,
per-request latency accounting, and the two serve-path contracts —
Request.stats key-schema parity across cache layouts, and greedy outputs
bitwise-identical with observability on or off."""
import json

import numpy as np
import jax
import pytest

from repro.configs import get_config, reduced
from repro.models import RunConfig, init_params
from repro.obs import (LAT_KEYS, NOOP, MetricsRegistry, NullMetrics,
                       Observability, RequestTimeline, SpanTracer, aggregate,
                       available_sinks, get_sink, latency_summary,
                       percentile, validate_chrome_trace)
from repro.serve.engine import Request, ServeEngine

RC = RunConfig(q_chunk=16, kv_chunk=16)


class VirtualClock:
    """Deterministic injectable clock: advances ``dt`` per read."""

    def __init__(self, dt=1.0):
        self.t = 0.0
        self.dt = dt

    def __call__(self):
        self.t += self.dt
        return self.t


# ---------------------------------------------------------------------------
# Metrics registry
# ---------------------------------------------------------------------------
def test_counters_and_labels_are_separate_series():
    m = MetricsRegistry()
    m.inc("serve/admitted")
    m.inc("serve/admitted", 2.0)
    m.inc("serve/recompiles", kind="decode_step")
    m.inc("serve/recompiles", kind="prefill_step")
    assert m.counter_value("serve/admitted") == 3.0
    assert m.counter_value("serve/recompiles", kind="decode_step") == 1.0
    assert m.counter_value("serve/recompiles", kind="prefill_step") == 1.0
    assert m.counter_value("serve/recompiles") == 0.0   # unlabeled series


def test_gauges_overwrite():
    m = MetricsRegistry()
    m.set_gauge("kv/blocks_in_use", 3)
    m.set_gauge("kv/blocks_in_use", 7)
    assert m.gauge_value("kv/blocks_in_use") == 7.0


def test_histogram_percentiles_nearest_rank():
    m = MetricsRegistry()
    for v in range(1, 101):
        m.observe("lat", float(v))
    (h,) = m.snapshot()["histograms"]
    assert h["count"] == 100 and h["min"] == 1.0 and h["max"] == 100.0
    assert h["p50"] == 50.0 and h["p99"] == 99.0
    assert percentile([3.0, 1.0, 2.0], 50) == 2.0
    assert percentile([5.0], 99) == 5.0


def test_snapshot_json_roundtrip(tmp_path):
    m = MetricsRegistry()
    m.inc("serve/steps", 4)
    m.observe("serve/ttft_s", 0.25)
    p = tmp_path / "metrics.json"
    text = m.to_json(p, extra={"latency": {"ttft_s": {"p50": 0.25}}})
    doc = json.loads(p.read_text())
    assert doc == json.loads(text)
    assert doc["counters"][0]["name"] == "serve/steps"
    assert doc["latency"]["ttft_s"]["p50"] == 0.25


def test_null_metrics_absorbs_everything():
    n = NullMetrics()
    n.inc("x")
    n.observe("y", 1.0)
    n.set_gauge("z", 2.0)
    assert n.snapshot() == {"counters": [], "gauges": [], "histograms": []}
    assert n.counter_value("x") == 0.0


def test_sink_registry():
    assert {"null", "memory"} <= set(available_sinks())
    assert get_sink("null") is NOOP
    assert get_sink("memory").metered
    with pytest.raises(ValueError, match="unknown observability sink"):
        get_sink("nope")


# ---------------------------------------------------------------------------
# Span tracer
# ---------------------------------------------------------------------------
def test_tracer_emits_valid_chrome_trace():
    clk = VirtualClock(dt=0.5)
    tr = SpanTracer(clock=clk)
    with tr.span("serve/step", step=0):
        with tr.span("serve/forward", tokens=2):
            pass
        tr.instant("recompile", kind="paged_step")
    doc = tr.to_chrome_trace()
    v = validate_chrome_trace(
        doc, required_names=("serve/step", "serve/forward", "recompile"))
    assert v["events"] == 3
    spans = {e["name"]: e for e in doc["traceEvents"] if e["ph"] == "X"}
    # inner span closed before the outer: strictly shorter duration
    assert spans["serve/forward"]["dur"] < spans["serve/step"]["dur"]
    assert spans["serve/forward"]["args"] == {"tokens": 2}


def test_validate_chrome_trace_rejects_garbage():
    with pytest.raises(AssertionError):
        validate_chrome_trace({"no": "envelope"})
    ok = SpanTracer(clock=VirtualClock())
    with ok.span("a"):
        pass
    with pytest.raises(AssertionError, match="missing"):
        validate_chrome_trace(ok.to_chrome_trace(), required_names=("b",))


def test_null_tracer_spans_are_free():
    with NOOP.tracer.span("anything", deep=1):
        NOOP.tracer.instant("x")
    assert NOOP.tracer.save("/nonexistent/never/written.json") is None


# ---------------------------------------------------------------------------
# Latency accounting
# ---------------------------------------------------------------------------
def test_request_timeline_virtual_clock():
    tl = RequestTimeline(submit=0.0, admit=1.0)
    for t in (3.0, 4.0, 6.0):
        tl.on_token(t)
    s = tl.finalize(end=7.0)
    assert set(s) == set(LAT_KEYS)
    assert s["lat/queue_wait_s"] == 1.0
    assert s["lat/ttft_s"] == 3.0            # first token - submit
    assert s["lat/tpot_s"] == 1.5            # (6 - 3) / 2 inter-token gaps
    assert s["lat/e2e_s"] == 7.0
    assert s["lat/decode_tokens"] == 3.0


def test_single_token_tpot_is_finite_zero():
    tl = RequestTimeline(submit=0.0, admit=0.0)
    tl.on_token(2.0)
    s = tl.finalize(end=2.0)
    assert s["lat/tpot_s"] == 0.0 and np.isfinite(s["lat/tpot_s"])


def test_aggregate_nearest_rank():
    a = aggregate([0.1 * i for i in range(1, 101)])
    assert a["n"] == 100
    assert a["p50"] == pytest.approx(5.0)
    assert a["p99"] == pytest.approx(9.9)
    assert aggregate([]) is None


# ---------------------------------------------------------------------------
# Straggler wiring (satellite: runtime/fault.py -> serve loop)
# ---------------------------------------------------------------------------
def test_slow_step_flagged_on_virtual_clock():
    clk = VirtualClock(dt=0.0)
    obs = Observability.memory(clock=clk, straggler_window=8,
                               straggler_factor=2.0)
    for step, dur in enumerate([1.0, 1.0, 1.0, 1.0, 10.0]):
        obs.step_begin(step)
        clk.t += dur
        obs.step_end(step, scope="serve")
    assert obs.metrics.counter_value("serve/slow_steps") == 1.0
    (ev,) = [e for e in obs.tracer.events if e["name"] == "slow_step"]
    assert ev["args"]["step"] == 4 and ev["args"]["slowdown"] == 10.0


# ---------------------------------------------------------------------------
# Serve-path contracts
# ---------------------------------------------------------------------------
def _mk_reqs(cfg, n, max_new=4):
    rng = np.random.default_rng(0)
    return [Request(rid=i,
                    prompt=rng.integers(0, cfg.vocab_size, 5).astype(np.int32),
                    max_new=max_new) for i in range(n)]


def _run(cfg, *, obs=None, kv_block_size=None):
    params = init_params(cfg, jax.random.key(0))
    eng = ServeEngine(cfg, params, slots=2, capacity=32, rc=RC,
                      kv_block_size=kv_block_size, obs=obs)
    reqs = _mk_reqs(cfg, 4)
    done = eng.run(reqs, max_steps=256)
    assert len(done) == len(reqs)
    return reqs, eng


DENSE = lambda: reduced(get_config("smollm-360m"), layers=2, d_model=32)
MOE = lambda: reduced(get_config("moonshot-v1-16b-a3b"), layers=2,
                      d_model=64, vocab=128)


@pytest.mark.parametrize("mk_cfg", [DENSE, MOE], ids=["dense", "moe"])
@pytest.mark.parametrize("kv_block", [None, 0], ids=["paged", "contiguous"])
def test_greedy_bitwise_identity_obs_on_off(mk_cfg, kv_block):
    """The overhead contract: attaching the full in-memory bundle must not
    change a single generated token (tracing adds no device-side ops)."""
    cfg = mk_cfg()
    base, _ = _run(cfg, obs=None, kv_block_size=kv_block)
    inst, _ = _run(cfg, obs=Observability.memory(), kv_block_size=kv_block)
    assert [r.out for r in base] == [r.out for r in inst]


@pytest.mark.parametrize("mk_cfg", [DENSE, MOE], ids=["dense", "moe"])
def test_request_stats_schema_parity_paged_vs_contiguous(mk_cfg):
    """Both cache layouts must materialize the SAME Request.stats key
    families (lat/* + serve/*) so downstream aggregation never branches
    on engine internals; every value stays finite."""
    cfg = mk_cfg()
    paged, eng = _run(cfg, kv_block_size=None)
    contig, _ = _run(cfg, kv_block_size=0)
    assert eng.paged
    for rp, rc_ in zip(paged, contig):
        assert set(rp.stats) == set(rc_.stats), (rp.stats, rc_.stats)
        assert set(LAT_KEYS) <= set(rp.stats)
        assert {"serve/prefix_hit_tokens", "serve/prefill_forwards"} \
            <= set(rp.stats)
        for r in (rp, rc_):
            assert all(np.isfinite(v) for v in r.stats.values()), r.stats
            assert r.stats["lat/decode_tokens"] == len(r.out)
            assert r.stats["lat/ttft_s"] <= r.stats["lat/e2e_s"]


def test_latency_summary_shape():
    reqs, _ = _run(DENSE())
    lat = latency_summary(reqs)
    assert set(lat) == {"ttft_s", "tpot_s", "queue_wait_s", "e2e_s"}
    for agg in lat.values():
        assert set(agg) == {"n", "mean", "p50", "p99"}
        assert agg["n"] == len(reqs)


def test_engine_metrics_and_trace_absorbed():
    obs = Observability.memory()
    reqs, eng = _run(MOE(), obs=obs)
    m = obs.metrics
    assert m.counter_value("serve/admitted") == len(reqs)
    assert m.counter_value("serve/completed") == len(reqs)
    assert m.counter_value("serve/steps") > 0
    # paged-cache telemetry mirrored as gauges each step
    assert m.gauge_value("kv/blocks_total") == eng.kv.n_blocks
    # per-request latency absorbed into histograms at retirement
    hists = {h["name"]: h for h in m.snapshot()["histograms"]}
    assert hists["serve/ttft_s"]["count"] == len(reqs)
    assert hists["serve/tpot_s"]["count"] == len(reqs)
    # the step timeline is a valid Chrome trace with the span skeleton
    v = validate_chrome_trace(
        obs.tracer.to_chrome_trace(),
        required_names=("serve/admit", "serve/step", "serve/assemble",
                        "serve/forward", "serve/host_sync", "serve/retire"))
    assert v["events"] > 0
    # straggler monitor saw every engine step
    assert len(obs.straggler.window) == m.counter_value("serve/steps")


def test_recompile_and_plan_trace_events():
    """The trace-time hook fires once per compiled shape: the MoE paged
    run compiles >= 1 step shape, counted under serve/recompiles and left
    as an instant in the trace."""
    obs = Observability.memory()
    _run(MOE(), obs=obs)
    m = obs.metrics
    assert m.counter_value("serve/recompiles", kind="paged_step") >= 1
    assert "recompile" in {e["name"] for e in obs.tracer.events}


def test_quantized_expert_bytes_gauge():
    cfg = MOE()
    params = init_params(cfg, jax.random.key(0))
    obs = Observability.memory()
    rc = RunConfig(q_chunk=16, kv_chunk=16, quant="int8_expert")
    eng = ServeEngine(cfg, params, slots=2, capacity=32, rc=rc, obs=obs)
    eng.run(_mk_reqs(cfg, 2))
    assert obs.metrics.gauge_value("serve/quant_expert_bytes",
                                   scheme="int8_expert") > 0


def test_dropped_requests_counted():
    cfg = DENSE()
    params = init_params(cfg, jax.random.key(0))
    obs = Observability.memory()
    eng = ServeEngine(cfg, params, slots=1, capacity=32, rc=RC, obs=obs)
    reqs = _mk_reqs(cfg, 2, max_new=8)
    eng.run(reqs, max_steps=3)
    assert eng.dropped
    assert obs.metrics.counter_value("serve/dropped") == len(eng.dropped)
    assert "serve/step_budget_exhausted" in \
        {e["name"] for e in obs.tracer.events}


# ---------------------------------------------------------------------------
# Engine spans: nesting, counts, the profiler's trace
# ---------------------------------------------------------------------------
def _serve_frontend(obs, n_reqs=3):
    """A tiny paged MoE engine behind the front end, run to the end."""
    from repro.serve.frontend import ServingFrontend
    cfg = MOE()
    params = init_params(cfg, jax.random.key(0))
    eng = ServeEngine(cfg, params, slots=2, capacity=32, rc=RC,
                      prefill_chunk=2, obs=obs)
    fe = ServingFrontend(eng)
    streamed = []
    reqs = [fe.submit(r.prompt, max_new=r.max_new, rid=r.rid,
                      on_token=lambda req, tok: streamed.append(tok))
            for r in _mk_reqs(cfg, n_reqs)]
    fe.drain(max_steps=256)
    assert all(r.done for r in reqs)
    return reqs, streamed


def _inside(inner, outer):
    return outer[0] <= inner[0] and inner[0] + inner[1] <= outer[0] + outer[1]


def test_engine_spans_nest_and_carry_host_counts():
    """Every piece of per-step host work is a span inside the one it is
    called from, and the forward span's counts add up: its rows split
    into decode and prefill rows, and over the run its emits are the
    tokens served (each streamed once)."""
    obs = Observability(tracer=SpanTracer())
    reqs, streamed = _serve_frontend(obs)
    spans = [(e["ts"], e["dur"], e["name"], e.get("args", {}))
             for e in obs.tracer.events if e["ph"] == "X"]
    by = {}
    for sp in spans:
        by.setdefault(sp[2], []).append(sp)
    for inner, outer in [("serve/schedule", "serve/poll"),
                         ("serve/admit", "serve/schedule"),
                         ("serve/prefix_probe", "serve/admit"),
                         ("serve/step", "serve/poll"),
                         ("serve/assemble", "serve/step"),
                         ("serve/inputs", "serve/assemble"),
                         ("serve/forward", "serve/step"),
                         ("serve/host_sync", "serve/step"),
                         ("serve/postprocess", "serve/step"),
                         ("serve/stream", "serve/postprocess"),
                         ("serve/prefix_register", "serve/postprocess"),
                         ("serve/retire", "serve/postprocess")]:
        assert by.get(inner), inner
        for sp in by[inner]:
            assert any(_inside(sp, o) for o in by[outer]), (inner, outer)
    fwd = [a for _, _, _, a in by["serve/forward"]]
    assert all(a["tokens"] == a["decode_rows"] + a["prefill_rows"]
               for a in fwd)
    served = sum(len(r.out) for r in reqs)
    assert sum(a["emits"] for a in fwd) == served == len(streamed)
    assert sum(a["args"]["tokens"] for a in obs.tracer.events
               if a["name"] == "serve/stream") == served
    assert [a["step"] for _, _, _, a in by["serve/step"]] == \
        list(range(len(by["serve/step"])))
    assert not any(e["name"] == "serve/retire" and e["ph"] == "i"
                   for e in obs.tracer.events)


def _profiled(tmp_path, obs):
    """Run the tiny engine under ``jax.profiler``; the host's
    ``serve/`` events of the profile, as (name, start_ns, dur_ns,
    stats)."""
    import pathlib

    from jax.profiler import ProfileData
    jax.profiler.start_trace(str(tmp_path))
    try:
        _serve_frontend(obs)
    finally:
        jax.profiler.stop_trace()
    (path,) = pathlib.Path(tmp_path).rglob("*.xplane.pb")
    pd = ProfileData.from_file(str(path))
    return [(e.name, e.start_ns, e.duration_ns, dict(e.stats))
            for plane in pd.planes if plane.name.startswith("/host:")
            for ln in plane.lines for e in ln.events
            if e.name.startswith("serve/")]


def test_spans_land_in_the_profilers_trace(tmp_path):
    """A SpanTracer's spans are profiler annotations: each in-memory
    span has one host event of its name in the profile, nested as the
    calls nest, with the span's host counts as stats."""
    obs = Observability(tracer=SpanTracer())
    host = _profiled(tmp_path, obs)
    mem = [e for e in obs.tracer.events if e["ph"] == "X"]
    count = lambda names: {n: names.count(n) for n in set(names)}
    assert count([h[0] for h in host]) == count([e["name"] for e in mem])
    by = {}
    for h in host:
        by.setdefault(h[0], []).append((h[1], h[2]))
    for inner, outer in [("serve/inputs", "serve/assemble"),
                         ("serve/assemble", "serve/step"),
                         ("serve/step", "serve/poll")]:
        for sp in by[inner]:
            assert any(_inside(sp, o) for o in by[outer]), (inner, outer)
    fwd_mem = [e["args"]["tokens"] for e in mem
               if e["name"] == "serve/forward"]
    fwd_host = [st for n, _, _, st in sorted(host, key=lambda h: h[1])
                if n == "serve/forward"]
    assert [int(st["tokens"]) for st in fwd_host] == fwd_mem
    steps = [st for n, _, _, st in sorted(host, key=lambda h: h[1])
             if n == "serve/step"]
    assert [int(st["step_num"]) for st in steps] == list(range(len(steps)))


def test_null_tracer_emits_nothing(tmp_path):
    """The default bundle puts nothing into a profile and hands back one
    shared context for every span."""
    assert NOOP.tracer.span("serve/x", a=1) is NOOP.tracer.step("s", 0)
    assert _profiled(tmp_path, NOOP) == []
    assert NOOP.tracer.events == []


def test_metric_feeding_work_needs_a_metrics_sink(monkeypatch):
    """A bundle that only traces reads no pool statistics per step; one
    with a metrics registry does."""
    from repro.serve.kv_cache import PagedKVCache
    calls = []
    stats = PagedKVCache.stats
    monkeypatch.setattr(PagedKVCache, "stats",
                        lambda self: calls.append(1) or stats(self))
    traced = Observability(tracer=SpanTracer())
    assert not traced.metered
    _run(MOE(), obs=traced)
    assert calls == []
    _run(MOE(), obs=Observability.memory())
    assert calls


# ---------------------------------------------------------------------------
# Train-loop wiring
# ---------------------------------------------------------------------------
def test_train_loop_emits_spans_and_metrics():
    from repro.optim.adamw import OptConfig
    from repro.train.loop import train

    cfg = reduced(get_config("smollm-360m"), layers=1, d_model=32)
    obs = Observability.memory()
    out = train(cfg, RC, OptConfig(lr=1e-3), steps=3, batch=2, seq=8,
                log=lambda s: None, obs=obs)
    assert len(out["history"]) > 0
    names = {e["name"] for e in obs.tracer.events}
    assert {"train/data", "train/step"} <= names
    assert obs.metrics.counter_value("train/steps_logged") > 0
    hists = {h["name"] for h in obs.metrics.snapshot()["histograms"]}
    assert any(n.startswith("train/") for n in hists)


# ---------------------------------------------------------------------------
# Device trace bracket
# ---------------------------------------------------------------------------
def test_device_trace_is_a_noop_without_a_logdir(monkeypatch):
    from repro.obs import device_trace

    def boom(*a, **k):
        raise AssertionError("profiler touched")
    monkeypatch.setattr(jax.profiler, "start_trace", boom)
    with device_trace(None):
        pass


@pytest.mark.parametrize("phase", ["start", "stop"])
def test_device_trace_raises_when_the_profiler_fails(monkeypatch, tmp_path,
                                                     phase):
    """A run asked for a device trace must not finish without one."""
    from repro.obs import device_trace

    def fail(*a, **k):
        raise RuntimeError(f"profiler {phase} failed")
    monkeypatch.setattr(jax.profiler, "start_trace",
                        fail if phase == "start" else lambda d: None)
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        fail if phase == "stop" else lambda: None)
    with pytest.raises(RuntimeError, match=f"profiler {phase} failed"):
        with device_trace(str(tmp_path)):
            pass
