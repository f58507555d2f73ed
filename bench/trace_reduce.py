"""From a profiler trace to device busy time, kernel time and idle gaps.

Two steps, kept apart so the second can be checked on a small recorded
trace (``data/trace_small.json``):

1. ``events(path)`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote
   and returns plain lists of ``(name, start_ns, dur_ns)``: the device's
   programs (the TPU plane's ``XLA Modules`` line, e.g.
   ``jit_paged_step(...)``), its operations (``XLA Ops``, named by their
   HLO instruction, e.g. ``%fused_gate_up.12 = f32[...] custom-call(...)``,
   a loop's body ops inside the loop's own op), and the host's spans from
   the benchmark's ``TraceAnnotation``\\ s (names starting ``bench.``).
2. ``reduce(ev, start_ns, end_ns)`` over a window: busy time (the union
   of the program intervals), time per op family (the instruction name
   without its number; loops left out, since their body ops are counted),
   each kernel's summed time (ops of exactly that family), and the idle
   gaps labelled by the innermost host span around each gap's middle,
   summed by label (``idle_gaps``) and the longest of them one by one.
"""
from __future__ import annotations

import bisect
import pathlib
import re

HOST_PREFIX = "bench."
CONTAINERS = ("while", "conditional", "call")


def events(path) -> dict:
    """Device programs and ops and benchmark host spans of one trace."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    ev = {"modules": [], "ops": [], "host": []}
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:0"):
            for ln in plane.lines:
                key = {"XLA Modules": "modules", "XLA Ops": "ops"}.get(
                    ln.name)
                if key:
                    ev[key] += [(e.name, e.start_ns, e.duration_ns)
                                for e in ln.events]
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                ev["host"] += [(e.name, e.start_ns, e.duration_ns)
                               for e in ln.events
                               if e.name.startswith(HOST_PREFIX)]
    return ev


def find_xplane(logdir) -> pathlib.Path:
    found = sorted(pathlib.Path(logdir).rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return found[-1]


def union(intervals) -> list:
    """Merge (a, b) intervals; returns the disjoint, sorted union."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [tuple(x) for x in out]


def op_family(name: str) -> str:
    """``%fused_gate_up.12 = f32[...] custom-call(...)`` ->
    ``fused_gate_up``; a module name keeps its text before ``(``."""
    head = name.split(" = ", 1)[0].lstrip("%").split("(", 1)[0].strip()
    return re.sub(r"[.\-_]\d+$", "", head)


def _clip(items, lo, hi):
    out = []
    for n, s, d in items:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            out.append((n, a, b))
    return out


class _Labels:
    """The innermost host span around a time ("host idle" if none).
    Spans nest (a step holds its forward and sync) and start in order,
    so only spans starting shortly before the time, and the longest
    ones, can hold it."""

    def __init__(self, host):
        self.spans = sorted((s, s + d, d, n) for n, s, d in host)
        self.starts = [x[0] for x in self.spans]
        self.longest = sorted(self.spans, key=lambda x: -x[2])[:32]

    def at(self, t) -> str:
        i = bisect.bisect_right(self.starts, t)
        best = None
        for s, e, d, n in self.spans[max(0, i - 64):i] + self.longest:
            if s <= t <= e and (best is None or d < best[0]):
                best = (d, n)
        return best[1] if best else "host idle"


def reduce(ev: dict, start_ns: float, end_ns: float, *,
           kernels=(), top: int = 10) -> dict:
    window = end_ns - start_ns
    busy = union((a, b) for _, a, b in _clip(ev["modules"], start_ns,
                                              end_ns))
    busy_ns = sum(b - a for a, b in busy)
    per_op: dict = {}
    for n, a, b in _clip(ev["ops"], start_ns, end_ns):
        fam = op_family(n)
        if fam not in CONTAINERS:
            per_op[fam] = per_op.get(fam, 0) + (b - a)
    gaps, prev = [], start_ns
    for a, b in busy + [(end_ns, end_ns)]:
        if a > prev:
            gaps.append((prev, a))
        prev = max(prev, b)
    labels = _Labels(ev["host"])
    labelled = [(labels.at((a + b) / 2), b - a) for a, b in gaps]
    labelled.sort(key=lambda x: -x[1])
    idle_by: dict = {}
    for n, g in labelled:
        idle_by[n] = idle_by.get(n, 0) + g
    return {
        "window_s": window / 1e9,
        "busy_s": busy_ns / 1e9,
        "idle_share": 1.0 - busy_ns / window,
        "kernel_s": {k: per_op.get(k, 0) / 1e9 for k in kernels},
        "device_ops": [[n, v / 1e9] for n, v in sorted(
            per_op.items(), key=lambda x: -x[1])[:top]],
        "idle_gaps": [[n, v / 1e9] for n, v in sorted(
            idle_by.items(), key=lambda x: -x[1])[:top]],
        "longest_gaps": [[n, g / 1e9] for n, g in labelled[:top]],
    }
