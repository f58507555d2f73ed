"""End-to-end system behaviour: train -> checkpoint -> restore -> serve
with the SAME weights, exercising the full stack (data pipeline, loop,
optimizer, checkpoint manager, serving engine) in one flow."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.configs import get_config, reduced
from repro.models import RunConfig, forward, init_cache
from repro.optim.adamw import OptConfig
from repro.serve.engine import Request, ServeEngine
from repro.train.loop import train

RC = RunConfig(q_chunk=16, kv_chunk=16, loss_chunk=32)


def test_train_checkpoint_serve_roundtrip(tmp_path):
    cfg = reduced(get_config("smollm-360m"), layers=2, d_model=64, vocab=64)
    opt = OptConfig(lr=1e-2, warmup_steps=5, total_steps=60,
                    weight_decay=0.0)
    out = train(cfg, RC, opt, steps=30, batch=8, seq=64,
                ckpt_dir=str(tmp_path), save_every=10, log_every=10,
                log=lambda s: None)
    assert out["history"][-1]["ce"] < out["history"][0]["ce"]

    # restore the final checkpoint into a fresh tree and serve with it
    from repro.checkpoint.manager import CheckpointManager
    from repro.train.step import init_train_state
    mgr = CheckpointManager(str(tmp_path))
    abstract = jax.eval_shape(
        lambda: init_train_state(cfg, jax.random.key(0), RC))
    state = mgr.restore(abstract)

    params = state["params"]
    # served greedy continuation == direct decode with trained params
    prompt = np.asarray([3, 1, 4, 1], np.int32)
    eng = ServeEngine(cfg, params, slots=1, capacity=32, rc=RC)
    req = Request(rid=0, prompt=prompt, max_new=4)
    eng.run([req])
    assert len(req.out) == 4

    # trained model should beat chance on its own Markov stream
    from repro.data.pipeline import make_batch
    from repro.models import loss_fn
    batch = make_batch(cfg, 8, 64, step=999, seed=1)
    loss, _ = loss_fn(params, cfg, RC,
                      {k: jnp.asarray(v) for k, v in batch.items()})
    assert float(loss) < 0.8 * np.log(cfg.vocab_size)


@pytest.mark.parametrize("placed", [False, True])
def test_compile_cache_goes_to_the_env_dir_or_one_fixed_path(monkeypatch,
                                                             placed):
    """``JAX_COMPILATION_CACHE_DIR`` is left to jax; without it the launch
    entry points use one fixed directory inside the checkout."""
    import pathlib
    from repro import launch
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: seen.update({k: v}))
    seen = {}
    if placed:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        launch.use_compile_cache()
        assert seen == {}
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        launch.use_compile_cache()
        repo = pathlib.Path(__file__).resolve().parents[1]
        assert seen == {"jax_compilation_cache_dir": str(repo / ".jax_cache")}
