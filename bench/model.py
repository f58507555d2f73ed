"""A configuration file -> the program's model config, weights and engine.

The benchmark makes the weights itself, from the run's seed, in one
jitted call on the device: the reference (``reference.py``) reads the
same arrays, so it takes nothing that the program has made.  Only the
layout of the parameter tree (its keys and shapes) comes from the
program, through ``jax.eval_shape`` of its initializer.

The engine is the paged ``ServeEngine`` with the settings of
``repro.launch.serve.build_engine`` (``q_chunk``/``kv_chunk`` 64, plan
statistics on, no autotune, no quantization, greedy sampling), over
these weights and the configuration's ``engine`` block.  The launcher
makes its own weights, so it is not called: ``build_engine`` here
mirrors its ``ServeEngine`` arguments, and a change to them there has
to be carried over.
"""
from __future__ import annotations

import json
import pathlib

import numpy as np

RMS_EPS_AS_RUN = 1e-6          # the program's RMSNorm epsilon (fixed)


def load(path) -> dict:
    return json.loads(pathlib.Path(path).read_text())


def rms_eps(c: dict) -> float:
    """The RMSNorm epsilon as run: ``as_run`` where the file states the
    program's departure from the published value, else the published
    one."""
    return c.get("as_run", {}).get("rms_norm_eps", c["rms_norm_eps"])


def model_config(c: dict):
    """The program's ``ModelConfig`` for configuration file ``c``, from
    its architecture's file (``archs.py``)."""
    import archs
    if rms_eps(c) != RMS_EPS_AS_RUN:
        raise ValueError(f"{c['name']}: the program's RMSNorm runs at eps "
                         f"{RMS_EPS_AS_RUN}, the file states {rms_eps(c)} "
                         "as run")
    return archs.program(c["model_type"]).program_config(c)


def gqa_config(c: dict, moe):
    """The program's ``ModelConfig`` of a decoder with grouped-KV
    attention (the configuration's heads, KV heads and ``head_dim``) and
    ``moe`` for its MoE layers."""
    from repro.configs.base import ModelConfig
    return ModelConfig(
        name=c["name"], family="moe", n_layers=c["num_hidden_layers"],
        d_model=c["hidden_size"], n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], d_ff=c["intermediate_size"],
        vocab_size=c["vocab_size"], head_dim=c["head_dim"],
        rope_theta=float(c["rope_theta"]),
        tie_embeddings=c["tie_word_embeddings"], moe=moe)


def param_shapes(cfg):
    """The program's parameter tree as shapes (no arrays made)."""
    import jax

    from repro.models import init_params
    return jax.eval_shape(lambda: init_params(cfg, jax.random.key(0)))


def seed_key(seed: int):
    """A jax key from any whole-number seed (64-bit seeds included)."""
    import jax
    return jax.random.key(
        int(np.random.SeedSequence(int(seed)).generate_state(1)[0]))


def _leaf_values(key, path, leaf):
    """Seeded values for one leaf: norm scales (the program's ``1 +
    scale`` form) around 0, the embedding unit normal, every matrix
    normal over the square root of its fan-in (the next-to-last axis)."""
    import jax
    import jax.numpy as jnp
    name = str(getattr(path[-1], "key", path[-1]))
    z = jax.random.normal(key, leaf.shape, jnp.float32)
    if name == "scale":
        z = 0.1 * z
    elif name != "embed":
        z = z * leaf.shape[-2] ** -0.5
    return z.astype(leaf.dtype)


def make_weights(cfg, seed: int, shapes=None):
    """Every weight, from ``seed``, on the default device, in one jitted
    call and in the dtype the program serves them in."""
    import jax
    shapes = shapes or param_shapes(cfg)
    flat, tree = jax.tree_util.tree_flatten_with_path(shapes)

    @jax.jit
    def make(key):
        keys = jax.random.split(key, len(flat))
        return jax.tree.unflatten(
            tree, [_leaf_values(keys[i], p, l)
                   for i, (p, l) in enumerate(flat)])
    return make(seed_key(seed))


def run_config(c: dict):
    from repro.models import RunConfig
    eng = c["engine"]
    return RunConfig(q_chunk=64, kv_chunk=64, executor=eng["executor"],
                     schedule_policy=eng["schedule_policy"], quant="none",
                     moe_stats=True, autotune=False,
                     paged_attn=eng["paged_attn"])


def build_engine(c: dict, cfg, params, obs=None):
    from repro.sampling import SamplingConfig
    from repro.serve.engine import ServeEngine
    eng = c["engine"]
    return ServeEngine(
        cfg, params, slots=eng["slots"], capacity=eng["capacity"],
        rc=run_config(c), admission=eng["admission"],
        kv_block_size=eng["kv_block_size"],
        prefix_cache=eng["prefix_cache"],
        prefill_chunk=eng["prefill_chunk"], obs=obs,
        sampling=SamplingConfig(method="greedy"))
