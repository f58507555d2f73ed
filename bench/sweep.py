"""Find a cell's knee: one set-up, then its traffic at rising rates.

    python bench/sweep.py --workload NAME --seed N --seconds S \\
        --rates 0.3 0.4 0.5 0.6

One process, one engine, and no pause between rates: the mix's lead-in
at the first rate, then ``--seconds`` at each rate in turn (a
staircase).  The client counts once a second the requests waiting for
a slot.  For each rate it prints one JSON line: the requests due, the
waiting count when the rate began and ended and its mean over each half
of the rate's time, the time per output token (median over the requests
that finished in it), the gaps between tokens (median and p90), the
output tokens per second and how late the client ran.  The knee is the
highest rate at which the waiting count does not grow; past it the
queue grows all through the rate's time.  The cell's rate is then
written into its traffic file.  Needs the chip, like ``run.py``.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

HERE = pathlib.Path(__file__).resolve().parent


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args()
    sys.path.insert(0, str(HERE.parent / "src"))
    sys.path.insert(0, str(HERE))
    import jax
    import numpy as np

    import client
    import model
    import run
    import traffic
    run.use_cache()
    if jax.devices()[0].platform != "tpu":
        run.fail("no TPU")
    found = run.cell_of(run.manifest(), args.workload)
    c = model.load(found["config_file"])
    mix = traffic.load(found["traffic_file"])
    jax.config.update("jax_default_matmul_precision",
                      c["precision"]["matmul"])
    t = time.perf_counter()
    cfg = model.model_config(c)
    engine = model.build_engine(c, cfg, model.make_weights(cfg, args.seed))
    run.warm_up(engine, run.step_rows(c))
    print(f"set-up {time.perf_counter() - t:.1f} s", file=sys.stderr,
          flush=True)

    from repro.serve.frontend import ServingFrontend
    fe = ServingFrontend(engine)
    rng = np.random.default_rng(args.seed)
    S, V = args.seconds, c["vocab_size"]

    def at(rate):
        return dict(mix, arrivals={"kind": "poisson", "rate_per_s": rate})
    lead = mix["arrivals"].get("lead_in_s", 0)
    m0 = at(args.rates[0])
    reqs = traffic.part(m0, traffic.count(m0, lead), None, rng, V) \
        if lead else []
    for k, rate in enumerate(args.rates):
        m = at(rate)
        reqs += traffic.part(m, traffic.count(m, S), k * S, rng, V)
    waiting = []

    def sample(now):
        waiting.append((now, len(fe.pending)))
    t0 = time.perf_counter() - min(r.due for r in reqs)
    recs, _ = client.drive(fe, reqs, t0=t0, seconds=S * len(args.rates),
                           sample=sample)
    for k, rate in enumerate(args.rates):
        a, b = t0 + k * S, t0 + (k + 1) * S
        w = [n for tw, n in waiting if a <= tw < b]
        h = len(w) // 2
        lat = client.latencies(recs, a, b)
        due = [r for r in recs if k * S <= r.due < (k + 1) * S]
        print(json.dumps({
            "rate_per_s": rate, "due": len(due),
            "waiting_first": w[0] if w else None,
            "waiting_last": w[-1] if w else None,
            "waiting_mean_1st_half": float(np.mean(w[:h])) if h else None,
            "waiting_mean_2nd_half": float(np.mean(w[h:])) if h else None,
            "finished": len(lat["tpot"]),
            "tpot_p50_ms": client.percentile(lat["tpot"], 50),
            "itl_p50_ms": client.percentile(lat["itl"], 50),
            "itl_p90_ms": client.percentile(lat["itl"], 90),
            "output_tok_s": client.tokens_in(recs, a, b) / S,
            "lag_p99_ms": client.percentile(
                client.lag_ms(due, t0), 99)}), flush=True)


if __name__ == "__main__":
    main()
