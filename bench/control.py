"""Readings for the limits of ``correct``: the program and its control.

    python bench/control.py --workload NAME --seconds S --seeds N1 N2 ...

One set-up for the cell; then, seed by seed, the cell's own traffic at
its own rate (its lead-in, then a short window) on weights made from
that seed, and the numbers ``run.py`` compares over the requests
finished by the close — for the program, and for the control: the
reference one precision below the configuration's (``reference.py``,
``ctl``) put in the program's place over the same prompts and served
tokens — each judged by ``check.verdict`` against the configuration's
limits.  One JSON line per seed.  The lower reading of a limit is the
largest the program gives over the seeds, the upper the smallest the
control gives.  Needs the chip.
"""
from __future__ import annotations

import argparse
import gc
import json
import pathlib
import sys

HERE = pathlib.Path(__file__).resolve().parent


def study(found: dict, seeds, seconds: float, emit=print) -> list:
    """The program's and the control's readings on each seed, each with
    the verdict ``check.verdict`` gives them (``correct``,
    ``control_correct``)."""
    import time

    import jax
    import numpy as np

    import check
    import client
    import model
    import run
    import traffic
    c = model.load(found["config_file"])
    mix = traffic.load(found["traffic_file"])
    jax.config.update("jax_default_matmul_precision",
                      c["precision"]["matmul"])
    cfg = model.model_config(c)
    shapes = model.param_shapes(cfg)
    engine = model.build_engine(c, cfg, model.make_weights(cfg, seeds[0],
                                                           shapes))
    run.warm_up(engine, run.step_rows(c))
    from repro.serve.frontend import ServingFrontend
    out = []
    params = None
    for seed in seeds:
        engine.params = params = None
        gc.collect()
        engine.params = params = model.make_weights(cfg, seed, shapes)
        reqs = traffic.generate(mix, seed=seed, seconds=seconds,
                                vocab=c["vocab_size"])
        fe = ServingFrontend(engine)
        t0 = time.perf_counter() - min(r.due for r in reqs)
        recs, _ = client.drive(fe, reqs, t0=t0, seconds=seconds)
        rng = np.random.default_rng([seed, 1])
        picked, kv_read = run.sample(recs, rng, c, engine)
        # free every slot and forget what was queued, so the next seed
        # starts on an idle engine with its request ids free
        while engine.n_active:
            engine._retire(engine.n_active - 1, decode_batch=0)
        engine._submit.clear()
        values = check.readings(c, params, picked, kv_read, control=True)
        values["seed"] = seed
        values["correct"] = check.verdict(c, values)[0]
        values["control_correct"] = check.verdict(c, dict(
            values, served_gap_max=values["control_served_gap_max"],
            kv_err_max=values["control_kv_err_max"]))[0]
        emit(json.dumps(values))
        out.append(values)
    return out


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args()
    sys.path.insert(0, str(HERE.parent / "src"))
    sys.path.insert(0, str(HERE))
    import jax

    import run
    run.use_cache()
    if jax.devices()[0].platform != "tpu":
        run.fail("no TPU")
    found = run.cell_of(run.manifest(), args.workload)
    study(found, args.seeds, args.seconds,
          emit=lambda s: print(s, flush=True))


if __name__ == "__main__":
    main()
