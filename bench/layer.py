"""Arithmetic shared by the per-layer metric readers (``metrics/``).

Each reader gets the run's record (``run``): the configuration ``c``, the
peaks, the request records, the window (``t0``, ``t_close``), the
programs jax lowered in the window and in set-up (``compiles``,
``setup_compiles``), and in a traced run the steps dispatched while the profiler ran, the engine's host
spans and the reduced trace (``check.steps_and_spans``).  A reader that
finds nothing to read returns None, and the metric is left out.
"""
from __future__ import annotations

import costs
from client import latencies, percentile

EXPERT_KERNELS = ("fused_gate_up", "grouped_gemm")
DISPATCH_KERNELS = ("router_topk", "permute", "unpermute")


def itl_p99_ms(run):
    """Every gap between consecutive tokens that ends in the window,
    p99: the far tail a reader of the stream sees (too wide run to run
    to bound end to end: a few slow steps move it)."""
    itl = latencies(run["recs"], run["t0"], run["t_close"])["itl"]
    return percentile(itl, 99) if itl else None


def host_ms_per_step(run):
    """The engine's host work per step: its ``serve/assemble`` and
    ``serve/postprocess`` spans, summed, over its ``serve/step`` spans,
    for the steps that began while the profiler ran."""
    if "spans" not in run:
        return None
    a, b = run["traced"]
    t0 = run["tracer_t0"]
    inside = [e for e in run["spans"]
              if a <= t0 + e["ts"] / 1e6 <= b]
    n = sum(1 for e in inside if e["name"] == "serve/step")
    host = sum(e["dur"] for e in inside
               if e["name"] in ("serve/assemble", "serve/postprocess"))
    return host / 1e3 / n if n else None


def pad_share(run):
    """Share of the expert rows computed that were padding:
    (padded - useful) / padded, summed over the traced steps."""
    steps = run.get("steps")
    if not steps:
        return None
    padded = sum(s["sched/padded_rows"] for s in steps)
    useful = sum(s["sched/useful_rows"] for s in steps)
    return 100.0 * (padded - useful) / padded if padded else None


def _moe_layers(run):
    return costs.dims(run["c"])["moe_layers"]


def roofline(run, kernels, cost):
    """Least time of the traced steps' calls of ``kernels`` over their
    summed device time, in percent."""
    steps = run.get("steps")
    if not steps:
        return None
    kt = sum(run["trace"]["kernel_s"].get(k, 0.0) for k in kernels)
    if kt <= 0:
        return None
    L = _moe_layers(run)
    least = 0.0
    for s in steps:
        t, _ = costs.least_time(*cost(run["c"], s, L), run["peak"])
        least += L * t
    return 100.0 * least / kt


def expert_cost(c, s, L):
    E = costs.dims(c)["E"]
    experts = min(E, s["sched/n_blocks_active"] / L)
    return costs.expert_gemms(c, s["sched/useful_rows"] / L, experts)


def dispatch_cost(c, s, L):
    return costs.moe_dispatch(c, s["rows"], s["sched/useful_rows"] / L)


def mfu(run):
    """Model FLOPs of the traced steps (every row at its context, the LM
    head for the rows that yielded a token) over the traced window at
    the peak."""
    steps = run.get("steps")
    if not steps:
        return None
    c = run["c"]
    flops = 0.0
    for s in steps:
        flops += sum(costs.token_flops(c, float(p) + 1, False)
                     for p in s["pos"])
        flops += s["emitted"] * (costs.token_flops(c, 1, True)
                                 - costs.token_flops(c, 1, False))
    window = run["trace"]["window_s"]
    return 100.0 * flops / (window * run["peak"]["flops_per_s"])


def idle_share(run):
    if "trace" not in run:
        return None
    return 100.0 * run["trace"]["idle_share"]
