"""The main-path Pallas kernels compile for the TPU v5e at real widths.

Interpret mode (every other kernel test) cannot see what the TPU
compiler refuses: blocks that are not whole tiles, unaligned slices,
unsupported loop forms.  These tests compile each kernel of the serving
path for a *described* v5e:2x2 topology — no chip is attached; the
compiler that ships with libtpu checks the program — at the widths of
moonshot-v1-16b-a3b (Moonlight-16B-A3B): d_model 2048, 64 routed experts
top-6 of width 1408, sigmoid gating, 16 KV heads of 128, a 256-token
step, block_m 128, and KV pages of 16 tokens.  Each compiled program
must contain the kernel as a Mosaic ``tpu_custom_call``.

The topology is described inside a module fixture, never at import: only
one process at a time may load libtpu, and under pytest-xdist every
worker imports this file.  Keep these tests in this one file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.core import apply_moe, dispatch_config, init_moe_params
from repro.kernels import ops
from repro.kernels.paged_attention import paged_decode_attention
from repro.scheduling import build_schedule

CFG = get_config("moonshot-v1-16b-a3b")
MOE = CFG.moe
T, D, E, K, F = 256, CFG.d_model, MOE.n_experts, MOE.top_k, MOE.d_ff_expert
M = MOE.block_m
HKV, HD, PAGE = CFG.n_kv_heads, CFG.head_dim, 16
DTYPES = [jnp.float32, jnp.bfloat16]


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def compiled_text(one_chip, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def schedule(idx):
    return build_schedule(idx, E, M, policy="dynamic")


def capacity():
    idx = jax.ShapeDtypeStruct((T, K), jnp.int32)
    return jax.eval_shape(schedule, idx).src_tok.shape[0]


def test_router_topk_compiles(one_chip):
    def f(logits):
        return ops.router_topk(logits, top_k=K, gating=MOE.gating,
                               norm_topk=MOE.norm_topk,
                               routed_scale=MOE.routed_scale,
                               interpret=False)
    hlo = compiled_text(one_chip, f, ((T, E), jnp.float32))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("dtype", DTYPES)
def test_permute_compiles(one_chip, dtype):
    def f(x, idx):
        return ops.permute(x, schedule(idx), interpret=False)
    hlo = compiled_text(one_chip, f, ((T, D), dtype), ((T, K), jnp.int32))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("dtype", DTYPES)
def test_fused_gate_up_compiles(one_chip, dtype):
    def f(xp, wg, wu, idx):
        return ops.fused_gate_up(xp, wg, wu, schedule(idx), interpret=False)
    hlo = compiled_text(one_chip, f, ((capacity(), D), dtype),
                        ((E, D, F), dtype), ((E, D, F), dtype),
                        ((T, K), jnp.int32))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("dtype", DTYPES)
def test_grouped_gemm_compiles(one_chip, dtype):
    def f(h, wd, idx):
        return ops.grouped_gemm(h, wd, schedule(idx), interpret=False)
    hlo = compiled_text(one_chip, f, ((capacity(), F), dtype),
                        ((E, F, D), dtype), ((T, K), jnp.int32))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("folded", [False, True])
@pytest.mark.parametrize("dtype", DTYPES)
def test_unpermute_compiles(one_chip, dtype, folded):
    def f(y, idx, w):
        return ops.unpermute(y, schedule(idx), None if folded else w,
                             interpret=False)
    hlo = compiled_text(one_chip, f, ((capacity(), D), dtype),
                        ((T, K), jnp.int32), ((T, K), jnp.float32))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("dtype", DTYPES)
def test_paged_decode_attention_compiles(one_chip, dtype):
    B, n_pool, per_row = 4, 64, 16        # 4 slots x 256-token capacity
    hlo = compiled_text(
        one_chip,
        lambda q, kp, vp, t, lim: paged_decode_attention(
            q, kp, vp, t, lim, q_pos=lim, causal=True),
        ((B, HKV, CFG.n_heads // HKV, HD), dtype),
        ((n_pool, PAGE, HKV, HD), dtype), ((n_pool, PAGE, HKV, HD), dtype),
        ((B, per_row), jnp.int32), ((B,), jnp.int32))
    assert "tpu_custom_call" in hlo


def moe_layer_hlo(one_chip, n_tokens):
    params = jax.eval_shape(
        lambda: init_moe_params(jax.random.key(0), MOE, D))
    dcfg = dispatch_config(MOE, executor="pallas", schedule_policy="dynamic",
                           interpret=False)
    leaves, tree = jax.tree.flatten(params)

    def f(x, *leaves):
        return apply_moe(jax.tree.unflatten(tree, leaves), x, dcfg)[0]
    return compiled_text(one_chip, f, ((n_tokens, D), jnp.float32),
                         *[(l.shape, l.dtype) for l in leaves])


def test_moe_layer_pallas_executor_compiles(one_chip):
    """One whole MoE layer (router -> permute -> fused gate/up -> grouped
    GEMM -> unpermute, plus the shared experts) through ``apply_moe``."""
    assert moe_layer_hlo(one_chip, T).count("tpu_custom_call") >= 5


@pytest.mark.parametrize("n_tokens", [1, 14, 32])
def test_moe_layer_compiles_at_decode_token_counts(one_chip, n_tokens):
    """The same layer at serving-step token counts, where the dynamic
    envelope is a few hundred rows (64-row permute blocks at 14)."""
    assert moe_layer_hlo(one_chip, n_tokens).count("tpu_custom_call") >= 5
