"""Grouped weight-gradient (tgmm) kernel — training backward, beyond-paper.

The paper is inference-only (its Limitation 3).  Training the dispatch
pipeline needs the transposed grouped GEMM:

    dW[e] = sum_{rows r of expert e} x[r]^T dy[r]        (E, K, N)

TPU formulation: grid (K-tiles, N-tiles, M-blocks) with M innermost, so
consecutive grid steps stream the (tile-aligned, expert-contiguous)
M-blocks of one expert through an fp32 VMEM accumulator and the output
block (expert, ki, ni) is flushed exactly once at each expert boundary —
the revisiting-accumulation pattern, driven by the same scalar-prefetch
schedule as the forward kernels.  Trailing inactive blocks carry the last
expert id (schedule clamp), so they extend — never reset — a real
expert's accumulation; experts that received zero tokens are zeroed by
the ops wrapper (their output blocks are never visited).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _kernel(block_expert_ref, block_active_ref,
            x_ref, dy_ref,
            out_ref,
            acc_ref, *, n_m: int):
    m = pl.program_id(2)
    be = block_expert_ref[m]
    prev = block_expert_ref[jnp.maximum(m - 1, 0)]
    first = (m == 0) | (be != prev)
    nxt = block_expert_ref[jnp.minimum(m + 1, n_m - 1)]
    last = (m == n_m - 1) | (nxt != be)
    active = block_active_ref[m] == 1

    @pl.when(first)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(active)
    def _accum():
        acc_ref[...] += jax.lax.dot_general(
            x_ref[...], dy_ref[...],
            dimension_numbers=(((0,), (0,)), ((), ())),   # x^T @ dy
            preferred_element_type=jnp.float32)

    @pl.when(last)
    def _flush():
        out_ref[0] = acc_ref[...].astype(out_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("n_experts", "block_m", "block_k", "block_n",
                     "interpret", "out_dtype"))
def grouped_wgrad(x: jnp.ndarray, dy: jnp.ndarray,
                  block_expert: jnp.ndarray, block_active: jnp.ndarray, *,
                  n_experts: int, block_m: int, block_k: int, block_n: int,
                  interpret: bool = False, out_dtype=None) -> jnp.ndarray:
    """x: (capacity, K); dy: (capacity, N) — both in the tile-aligned
    expert-contiguous layout -> dW: (E, K, N)."""
    capacity, K = x.shape
    _, N = dy.shape
    assert capacity % block_m == 0 and K % block_k == 0 and N % block_n == 0
    n_m = capacity // block_m

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(K // block_k, N // block_n, n_m),
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda ki, ni, m, be, ba: (m, ki)),
            pl.BlockSpec((block_m, block_n), lambda ki, ni, m, be, ba: (m, ni)),
        ],
        out_specs=pl.BlockSpec(
            (1, block_k, block_n), lambda ki, ni, m, be, ba: (be[m], ki, ni)),
        scratch_shapes=[pltpu.VMEM((block_k, block_n), jnp.float32)],
    )
    fn = pl.pallas_call(
        functools.partial(_kernel, n_m=n_m),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_experts, K, N),
                                       out_dtype or jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )
    return fn(block_expert, block_active, x, dy)
