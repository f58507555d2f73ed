"""Run one benchmark cell once on the chip and print its result line.

    python bench/run.py --workload NAME --seed N --seconds S --trace 0|1

From the root of a checkout that holds ``BENCHMARK.json``, ``bench/``
and the program (``src/repro``).  The cell names a configuration
(``bench/configs/<config>.json``) and a traffic mix
(``bench/traffic/<traffic>.json``); its per-layer metrics are readers
``bench/metrics/<metric>.py``, each found by its name in the manifest.

One run: make the weights from the seed on the device; build the paged
serving engine; compile and run once every step shape the cell can use;
drive the traffic's lead-in open-loop, so the engine is under the mix's
load (set-up ends when the window opens); drive the cell's traffic for
``--seconds`` more; read the peak device memory; free the engine; check
a sample of the served tokens against the float32 reference; print the
numbers compared beside their limits on standard error, and one JSON
line on standard output.  ``--trace 1`` also records a profiler trace
of the window and prints the cell's per-layer metrics instead of its
end-to-end ones.

Fails, printing no result, when JAX finds no TPU or fewer chips than the
cell asks for, or when the program is not beside the benchmark.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse                                            # noqa: E402
import gc                                                  # noqa: E402
import importlib.util                                      # noqa: E402
import json                                                # noqa: E402
import os                                                  # noqa: E402
import pathlib                                             # noqa: E402
import shutil                                              # noqa: E402
import sys                                                 # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent
OUT = HERE / "out"                  # traces of this checkout's runs
# the serving step's kernels, as the trace names them
KERNELS = ("router_topk", "permute", "unpermute", "fused_gate_up",
           "grouped_gemm", "paged_decode_attention")


def fail(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr)
    sys.exit(2)


def log(msg: str) -> None:
    print(f"bench: [{time.perf_counter() - T_START:.1f} s] {msg}",
          file=sys.stderr, flush=True)


def manifest() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def cell_of(man: dict, workload: str) -> dict:
    """The cell, its configuration entry, traffic file and the
    per-layer metrics it reports, all found by name."""
    cells = {w["name"]: w for w in man["workloads"]}
    if workload not in cells:
        fail(f"no workload {workload!r}; known: {sorted(cells)}")
    w = cells[workload]
    conf = {c["name"]: c for c in man["configs"]}[w["config"]]
    e2e = [m for m in man["end_to_end"]
           if workload in m.get("workloads", [workload])]
    names = {m["name"] for m in e2e}
    layer = [m for m in man["per_layer"]
             if (workload in m["workloads"] if "workloads" in m
                 else m["moves"] in names)]
    return {"cell": w, "config_file": ROOT / conf["file"],
            "traffic_file": HERE / "traffic" / f"{w['traffic']}.json",
            "end_to_end": e2e, "per_layer": layer}


def reader(name: str):
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name}", HERE / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def use_cache() -> str:
    """JAX's persistent compilation cache, in one fixed directory inside
    the checkout (the program is given no other)."""
    import jax
    path = str(HERE / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileEvents:
    """Times of jax's lowering events (one per program it traces and
    lowers, whether the compiled code then comes from the cache), and the
    seconds and counts of each compile event, for the set-up's log."""

    def __init__(self):
        import jax
        self.times: list[float] = []
        self.totals: dict = {}
        jax.monitoring.register_event_duration_secs_listener(self._on)
        jax.monitoring.register_event_listener(
            lambda event, **_: self._count(event))

    def _count(self, event):
        if "compil" in event:
            n, s = self.totals.get(event, (0, 0.0))
            self.totals[event] = (n + 1, s)

    def _on(self, event, duration, **_):
        if event == "/jax/core/compile/jaxpr_to_mlir_module_duration":
            self.times.append(time.perf_counter())
        if "compil" in event:
            n, s = self.totals.get(event, (0, 0.0))
            self.totals[event] = (n + 1, s + duration)

    def between(self, a: float, b: float = float("inf")) -> int:
        return sum(1 for t in self.times if a <= t <= b)

    def summary(self) -> str:
        return "; ".join(f"{e.rsplit('/', 1)[-1]} {n}x {s:.1f} s"
                         for e, (n, s) in sorted(self.totals.items()))


def step_rows(c: dict) -> range:
    """The token counts a step of this cell can take: each active slot
    adds its decode token or a prefill chunk (the program compiles its
    step once for each count)."""
    eng = c["engine"]
    return range(1, eng["slots"] * eng["prefill_chunk"] + 1)


def warm_up(engine, rows) -> None:
    """Compile and run once the engine's step at each token count in
    ``rows``, from inputs made as the engine makes them (lists of ints,
    so their conversions compile too); the results are dropped, so the
    engine's cache is untouched."""
    import jax.numpy as jnp
    import numpy as np
    nb = engine.kv.blocks_per_slot
    for T in rows:
        col = jnp.asarray([0] * T, jnp.int32)
        out = engine._pstep(
            engine.params, engine.kv.pools,
            engine._batch(jnp.asarray([[0]] * T, jnp.int32)), col,
            jnp.asarray(np.zeros((T, nb), np.int32)),
            jnp.asarray([-1] * T, jnp.int32), col, col)
        out[0].block_until_ready()
        del out


def sample(recs, rng, c: dict, engine) -> tuple:
    """Finished requests to check, in order, and the KV the engine still
    holds for each prompt: the one with the most served tokens, then
    others in an order drawn from the seed, until twice the sample's
    minimum tokens and KV rows are covered, or ``max_requests`` requests.
    ``check.readings`` takes them in order until the positions it can
    compare (near-ties left out) reach the minimums.  The pool reuses
    the blocks of requests that finished long ago, so some prompts read
    back nothing: once the tokens are covered, those are passed over."""
    import check
    lim = c["correct"]["sample"]
    done = [r for r in recs if r.done and r.out]
    if not done:
        return [], []
    longest = max(done, key=lambda r: len(r.out))
    rest = [r for r in done if r is not longest]
    order = [longest] + [rest[j] for j in rng.permutation(len(rest))]
    picked, kv_read, tokens, rows = [], [], 0, 0
    for r in order:
        if len(picked) >= lim["max_requests"] or (
                tokens >= 2 * lim["min_tokens"]
                and rows >= 2 * lim["min_kv_rows"]):
            break
        kv = check.program_kv(engine, r.prompt)
        if tokens >= 2 * lim["min_tokens"] and not kv:
            continue
        picked.append(r)
        kv_read.append(kv)
        tokens += len(r.out)
        rows += kv[0][0].shape[0] if kv else 0
    return picked, kv_read


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    if not (ROOT / "src" / "repro").is_dir():
        fail(f"no program (src/repro) beside the benchmark in {ROOT}")
    man = manifest()
    found = cell_of(man, args.workload)
    cell = found["cell"]
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(HERE))
    # the TPU runtime's own logs would go to a fixed path outside the
    # checkout
    os.environ.setdefault("TPU_LOG_DIR", "disabled")

    import jax
    use_cache()

    devices = jax.devices()
    d0 = devices[0]
    if d0.platform != "tpu":
        fail(f"no TPU: jax found {d0.platform} devices")
    if len(devices) < cell["chips"]:
        fail(f"cell {cell['name']} needs {cell['chips']} chips, jax found "
             f"{len(devices)}")
    import peaks
    line = execute(found, args, devices[:cell["chips"]],
                   peaks.peaks(d0.device_kind))
    print(json.dumps(line))


def execute(found: dict, args, devices, peak: dict) -> dict:
    """One run of the cell on ``devices``; returns the result line."""
    import jax
    import numpy as np

    import check
    import client
    import model
    import traffic
    cell = found["cell"]
    d0 = devices[0]
    c = model.load(found["config_file"])
    mix = traffic.load(found["traffic_file"])
    jax.config.update("jax_default_matmul_precision",
                      c["precision"]["matmul"])
    events = CompileEvents()
    cfg = model.model_config(c)
    params = model.make_weights(cfg, args.seed)
    jax.block_until_ready(params)
    log("weights made")
    obs = None
    if args.trace:
        from repro.obs import Observability, SpanTracer
        obs = Observability(tracer=SpanTracer())
    engine = model.build_engine(c, cfg, params, obs)
    log("engine built")
    warm_up(engine, step_rows(c))
    log(f"step shapes warm: {events.summary()}")
    reqs = traffic.generate(mix, seed=args.seed, seconds=args.seconds,
                            vocab=c["vocab_size"])

    from repro.serve.frontend import ServingFrontend
    fe = ServingFrontend(engine)
    ann = client.annotate(bool(args.trace))
    tap = client.StepTap(engine, time.perf_counter, ann) \
        if args.trace else None
    window = check.TraceWindow(OUT / f"{cell['name']}.{args.seed}") \
        if args.trace else None
    t_lead = time.perf_counter()
    t0 = t_lead + traffic.lead_in(mix)
    setup_compiles = events.between(T_START, t_lead)
    recs, t_close = client.drive(fe, reqs, t0=t0, seconds=args.seconds,
                                 trace=window, tap=tap)
    setup_s = t0 - T_START
    compiles = events.between(t0, t_close)
    memory_peak = max(d.memory_stats()["peak_bytes_in_use"]
                      for d in devices)

    run = {"c": c, "peak": peak, "recs": recs, "t0": t0,
           "t_close": t_close, "compiles": compiles,
           "setup_compiles": setup_compiles}
    log(f"set-up {setup_s:.1f} s ({t0 - t_lead:.1f} s of it the lead-in); "
        "window closed")
    if args.trace:
        run.update(check.steps_and_spans(tap, engine, window, KERNELS))
        shutil.rmtree(window.logdir, ignore_errors=True)
        log("trace reduced")
    rng = np.random.default_rng([args.seed, 1])
    picked, kv_read = sample(recs, rng, c, engine)
    engine.kv.pools = None
    del fe, engine, tap
    gc.collect()
    values = check.readings(c, params, picked, kv_read)
    log(f"reference compared over {values['requests']} requests")
    del params
    correct, compared = check.verdict(c, values)
    lat = client.latencies(recs, t0, t_close)
    attempted = lat["n_due"]
    failed = sum(1 for r in recs if r.failed and r.due >= 0)
    refused = sum(1 for r in recs if r.failed)      # lead-in's as well
    correct = correct and refused == 0

    if args.trace:
        metrics = {}
        for m in found["per_layer"]:
            v = reader(m["name"])(run)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        e2e = {
            "setup_s": setup_s,
            "tpot_p50_ms": client.percentile(lat["tpot"], 50),
            "itl_p90_ms": client.percentile(lat["itl"], 90),
        }
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in found["end_to_end"]}
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devices), "memory_peak_bytes": memory_peak}
    line = {"correct": correct, "attempted": attempted, "failed": failed,
            "metrics": metrics, "device": device}
    if args.trace:
        red = run["trace"]
        device.update(busy_s=red["busy_s"], window_s=red["window_s"])
        line["breakdown"] = {"device_ops": red["device_ops"],
                             "idle_gaps": red["idle_gaps"]}
    print(f"bench: {cell['name']} seed {args.seed}: {attempted} requests "
          f"due in the window, {failed} failed, {len(lat['tpot'])} "
          f"finished in it, {len(lat['itl'])} token gaps in it, "
          f"{compiles} compiles in it, {setup_compiles} in set-up; client "
          f"lag p99 {client.percentile(client.lag_ms(recs, t0), 99):.3f} "
          f"ms; setup {setup_s:.3f} s", file=sys.stderr)
    compared.append({"name": "failed_requests", "value": refused,
                     "limit": 0})
    for x in compared:
        print(f"check: {x['name']} = {x['value']!r} (limit {x['limit']!r})",
              file=sys.stderr)
    line["compared"] = compared
    return line


if __name__ == "__main__":
    main()
