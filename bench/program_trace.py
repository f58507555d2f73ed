"""The device's idle time, charged to the program's own spans.

The serving engine's spans (``serve/...``, ``repro.obs``) are profiler
annotations too, so a profile of a run holds them on the host's line, on
the same clock as the device's programs.  Over a window:

- ``split`` cuts each idle gap of the device (the window less the union
  of its program intervals) at every span boundary inside the gap and
  charges each piece to the innermost ``serve/`` span around it, or to
  ``outside`` where no span is open; it also counts the ``serve/step``
  spans that begin in the window;
- ``per_step`` sums the pieces into three groups, each in milliseconds
  per step: the engine's host work (``host``: the scheduling pass,
  admission, the prefix probe, the step's own bookkeeping, assembly and
  its input transfers, postprocessing, streaming, prefix registration,
  retirement), the dispatch of the jitted step and the wait for its
  result (``dispatch``: ``serve/forward``, ``serve/host_sync``), and the
  front end (``frontend``: ``serve/poll``'s own time and ``outside``).
  The three times the step count are the window's idle time.

    python bench/program_trace.py LOGDIR

prints the split of the newest profile under ``LOGDIR`` (a benchmark
run's, or ``repro.launch.serve --device-trace``'s) over the window the
benchmark marked (``bench.traced``), or else from the device's first
program to its last.
"""
from __future__ import annotations

import argparse
import bisect

import trace_reduce

PREFIX = "serve/"
OUTSIDE = "outside"
STEP = "serve/step"
DISPATCH = ("serve/forward", "serve/host_sync")
FRONTEND = ("serve/poll", OUTSIDE)


def events(path) -> dict:
    """``trace_reduce.events`` of the profile, with the program's spans
    under ``program``: ``(name, start_ns, dur_ns)`` of the host events
    whose names start with ``serve/`` (a second pass over the file)."""
    from jax.profiler import ProfileData
    ev = trace_reduce.events(path)
    pd = ProfileData.from_file(str(path))
    ev["program"] = [(e.name, e.start_ns, e.duration_ns)
                     for plane in pd.planes
                     if plane.name.startswith("/host:")
                     for ln in plane.lines for e in ln.events
                     if e.name.startswith(PREFIX)]
    return ev


def idle_gaps(modules, start_ns, end_ns) -> list:
    """The window less the union of the device's program intervals."""
    busy = trace_reduce.union(
        (max(s, start_ns), min(s + d, end_ns)) for _, s, d in modules
        if s < end_ns and s + d > start_ns)
    gaps, prev = [], start_ns
    for a, b in busy + [(end_ns, end_ns)]:
        if a > prev:
            gaps.append((prev, a))
        prev = max(prev, b)
    return gaps


def split(ev: dict, start_ns, end_ns) -> dict:
    """Idle seconds by innermost program span (``idle_s``) and the
    ``serve/step`` spans that begin in the window (``steps``).  Spans
    nest, so the innermost of those around a piece is the one that
    began last (of two that began together, the shorter)."""
    spans = sorted((max(s, start_ns), min(s + d, end_ns), s, -(s + d), n)
                   for n, s, d in ev["program"]
                   if s < end_ns and s + d > start_ns)
    starts = [sp[0] for sp in spans]
    idle: dict = {}
    active, nxt = [], 0
    for a, b in idle_gaps(ev["modules"], start_ns, end_ns):
        hi = bisect.bisect_left(starts, b, lo=nxt)
        active = [sp for sp in active if sp[1] > a] + [
            sp for sp in spans[nxt:hi] if sp[1] > a]
        nxt = hi
        cuts = sorted({a, b} | {t for sp in active for t in sp[:2]
                                if a < t < b})
        for c, d in zip(cuts, cuts[1:]):
            around = [sp for sp in active if sp[0] <= c and sp[1] >= d]
            name = max(around, key=lambda sp: sp[2:4])[4] if around \
                else OUTSIDE
            idle[name] = idle.get(name, 0) + (d - c)
    steps = sum(1 for n, s, _ in ev["program"]
                if n == STEP and start_ns <= s < end_ns)
    return {"idle_s": {n: v / 1e9 for n, v in sorted(
                idle.items(), key=lambda x: -x[1])},
            "steps": steps}


def group(name: str) -> str:
    if name in DISPATCH:
        return "dispatch"
    if name in FRONTEND:
        return "frontend"
    return "host"


def per_step(res: dict):
    """Idle milliseconds per step of each group; None with no step."""
    n = res["steps"]
    if not n:
        return None
    out = {"host": 0.0, "dispatch": 0.0, "frontend": 0.0}
    for name, s in res["idle_s"].items():
        out[group(name)] += s * 1e3 / n
    return out


def table(res: dict) -> str:
    """One line per span: idle seconds, ms per step, its group."""
    n = max(res["steps"], 1)
    lines = [f"{'span':<24} {'idle s':>10} {'ms/step':>9}  group"]
    lines += [f"{name:<24} {s:>10.6f} {s * 1e3 / n:>9.4f}  {group(name)}"
              for name, s in res["idle_s"].items()]
    return "\n".join(lines)


def window(ev: dict) -> tuple:
    """The benchmark's ``bench.traced`` span, or the device's first
    program to its last."""
    for name, s, d in ev["host"]:
        if name == "bench.traced":
            return s, s + d
    return (min(s for _, s, _ in ev["modules"]),
            max(s + d for _, s, d in ev["modules"]))


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("logdir")
    args = ap.parse_args()
    ev = events(trace_reduce.find_xplane(args.logdir))
    if not ev["modules"]:
        raise SystemExit(f"no TPU programs in the profile under {args.logdir}")
    lo, hi = window(ev)
    res = split(ev, lo, hi)
    red = trace_reduce.reduce(ev, lo, hi)
    print(table(res))
    print(f"steps {res['steps']}; idle {sum(res['idle_s'].values()):.6f} s "
          f"of a {red['window_s']:.6f}-s window (trace_reduce: "
          f"{red['window_s'] - red['busy_s']:.6f} s)")
    print(f"idle ms per step: {per_step(res)}")


if __name__ == "__main__":
    main()
