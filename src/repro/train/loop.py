"""Training loop: data -> jitted step -> metrics -> async checkpoints,
with straggler monitoring, failure injection hooks, and resume-on-restart
(optionally onto a different mesh — elastic)."""
from __future__ import annotations

import time
from typing import Any, Callable, Dict, Optional

import jax
import numpy as np

from repro.checkpoint.manager import CheckpointManager
from repro.configs.base import ModelConfig
from repro.data.pipeline import device_batch, make_batch
from repro.models.lm import RunConfig
from repro.obs import NOOP
from repro.optim.adamw import OptConfig
from repro.runtime.fault import FailureInjector, StragglerMonitor
from repro.train.step import init_train_state, make_train_step


def train(cfg: ModelConfig, rc: RunConfig, opt: OptConfig, *,
          steps: int, batch: int, seq: int, accum: int = 1,
          ckpt_dir: Optional[str] = None, save_every: int = 20,
          mesh=None, state_shardings=None, batch_shardings=None,
          fail_at: Optional[int] = None, seed: int = 0,
          log_every: int = 10, log: Callable[[str], None] = print,
          obs=None) -> Dict:
    """Returns {"state", "history", "stragglers", "resumed_from"}.

    ``obs`` (repro.obs.Observability, default NOOP) adds the same
    step-timeline spans the serve engine emits — ``train/data`` /
    ``train/step`` / ``train/checkpoint`` — plus ``train/*`` metric
    observations at each logged step; the loop's own StragglerMonitor
    keeps driving the log line either way."""
    obs = obs or NOOP
    manager = CheckpointManager(ckpt_dir) if ckpt_dir else None
    injector = FailureInjector(fail_at)
    monitor = StragglerMonitor()

    step_fn = make_train_step(cfg, rc, opt, accum_steps=accum)
    if mesh is not None:
        step_fn = jax.jit(step_fn, in_shardings=(state_shardings,
                                                 batch_shardings),
                          out_shardings=(state_shardings, None))
    else:
        step_fn = jax.jit(step_fn)

    start = 0
    resumed_from = None
    abstract = jax.eval_shape(
        lambda: init_train_state(cfg, jax.random.key(seed), rc))
    if manager is not None and manager.latest_step() is not None:
        state = manager.restore(abstract, shardings=state_shardings)
        start = manager.latest_step() + 1
        resumed_from = start - 1
        log(f"[train] resumed from step {resumed_from}")
    else:
        state = init_train_state(cfg, jax.random.key(seed), rc)
        if state_shardings is not None:
            state = jax.device_put(state, state_shardings)

    history = []
    try:
        for step in range(start, steps):
            monitor.start_step(step)
            obs.step_begin(step)
            injector.maybe_fail(step)
            with obs.tracer.span("train/data", step=step):
                b = make_batch(cfg, batch, seq, step=step, accum=accum,
                               seed=seed + 1)
                b = device_batch(b, batch_shardings)
            with obs.tracer.span("train/step", step=step):
                state, metrics = step_fn(state, b)
            flag = monitor.end_step()
            obs.step_end(step, scope="train")
            if flag:
                log(f"[straggler] step {flag['step']} "
                    f"{flag['slowdown']:.1f}x median")
            if step % log_every == 0 or step == steps - 1:
                m = {k: float(np.asarray(v)) for k, v in metrics.items()}
                history.append({"step": step, **m})
                if obs.metered:
                    obs.metrics.inc("train/steps_logged")
                    obs.metrics.observe_many("train/", m)
                log(f"[train] step {step:5d} loss {m.get('loss', 0):.4f} "
                    f"ce {m.get('ce', 0):.4f} gnorm "
                    f"{m.get('grad_norm', 0):.3f}")
            if manager is not None and step % save_every == 0 and step > 0:
                with obs.tracer.span("train/checkpoint", step=step):
                    manager.save(step, state)
    finally:
        if manager is not None:
            manager.wait()
    if manager is not None:
        manager.save(steps - 1, state)
        manager.wait()
    return {"state": state, "history": history,
            "stragglers": monitor.flagged, "resumed_from": resumed_from}
