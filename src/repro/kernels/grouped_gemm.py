"""Block-scheduled grouped GEMM — the paper's §3.2, TPU-native.

One ``pallas_call`` computes ``out[block i] = x[block i] @ w[expert(i)]`` for
every M-tile in the tile-aligned expert-contiguous layout.  The schedule
(block->expert, block->active) is passed as scalar-prefetch operands so the
weight ``BlockSpec.index_map`` selects each block's expert weights while the
DMA pipeline is still ahead of compute — the TPU replacement for the paper's
precomputed (expert_id, token_offset) grid mapping.

Optional epilogue: per-row scale (the top-k combine weight) fused into the
down projection — possible here because Pallas epilogues are ordinary vector
code (the paper's Triton version could not, its Limitation 1).

Quantized weights (DESIGN.md §8): ``w_format`` selects in-kernel dequant of
each DMA'd weight block — ``"int8"`` (payload int8, per-(expert, channel)
``w_scale`` multiply in VREGs) or ``"int4"`` (two-nibbles-per-byte payload
packed along K; sign-extend + row-interleave + scale in VREGs).  Only the
compressed bytes ever cross HBM->VMEM; the dense expert stack exists one
block at a time, right before its MXU issue.  ``w_format="dense"`` is the
original kernel unchanged (bitwise).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.quantization.schemes import unpack_int4


def dequant_weight_block(wq, ws, w_format: str, dtype):
    """Expand one gathered weight block to ``dtype`` inside the kernel.

    wq: (bk, bn) dense, (bk, bn) int8, or (bk//2, bn) int8 nibble-packed;
    ws: (1, bn) f32 per-output-channel scales (None for dense).
    Uses the SAME unpack/scale primitives as the jnp schemes
    (repro.quantization.schemes), so the Pallas and xla executors produce
    bit-identical dequantized blocks.
    """
    if w_format == "dense":
        return wq
    if w_format == "int4":
        wq = unpack_int4(wq)
    return (wq.astype(jnp.float32) * ws).astype(dtype)


def _kernel(block_expert_ref, block_active_ref,   # scalar prefetch
            x_ref, w_ref, ws_ref, scale_ref,      # inputs (ws/scale opt.)
            out_ref,                              # output
            acc_ref,                              # scratch
            *, n_k: int, has_scale: bool, w_format: str):
    m, _, k = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    active = block_active_ref[m] == 1

    @pl.when(k == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(active)
    def _accum():
        w = dequant_weight_block(
            w_ref[0], None if ws_ref is None else ws_ref[...],
            w_format, x_ref.dtype)
        acc_ref[...] += jnp.dot(x_ref[...], w,
                                preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _epilogue():
        acc = acc_ref[...]
        if has_scale:
            acc = acc * scale_ref[...].astype(jnp.float32)
        out_ref[...] = acc.astype(out_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("block_m", "block_n", "block_k", "interpret",
                     "out_dtype", "w_format"))
def grouped_gemm(x: jnp.ndarray, w: jnp.ndarray,
                 block_expert: jnp.ndarray, block_active: jnp.ndarray,
                 row_scale: jnp.ndarray | None = None,
                 w_scale: jnp.ndarray | None = None, *,
                 block_m: int, block_n: int, block_k: int,
                 w_format: str = "dense",
                 interpret: bool = False, out_dtype=None) -> jnp.ndarray:
    """x: (capacity, K) tile-aligned expert-contiguous; w: (E, K, N) dense
    or the scheme's packed payload ((E, K, N) int8 / (E, K//2, N) int8);
    w_scale: (E, N) f32 per-channel scales (required unless dense);
    block_expert/block_active: (capacity // block_m,);
    row_scale: optional (capacity,) fused epilogue scale -> (capacity, N).
    ``block_k`` is in LOGICAL K rows (the packed payload DMAs block_k//2)."""
    capacity, K = x.shape
    N = w.shape[-1]
    pack = 2 if w_format == "int4" else 1
    assert w.shape[1] * pack == K, (w.shape, K, w_format)
    assert (w_scale is not None) == (w_format != "dense"), w_format
    assert capacity % block_m == 0 and K % block_k == 0 and N % block_n == 0, (
        f"shape {(capacity, K, N)} not divisible by blocks "
        f"{(block_m, block_k, block_n)}")
    assert block_k % pack == 0, (block_k, w_format)
    n_m, n_n, n_k = capacity // block_m, N // block_n, K // block_k
    has_scale = row_scale is not None
    quant = w_format != "dense"

    in_specs = [
        pl.BlockSpec((block_m, block_k), lambda m, n, k, be, ba: (m, k)),
        pl.BlockSpec((1, block_k // pack, block_n),
                     lambda m, n, k, be, ba: (be[m], k, n)),
    ]
    operands = [x, w]
    if quant:
        in_specs.append(
            pl.BlockSpec((1, block_n), lambda m, n, k, be, ba: (be[m], n)))
        operands.append(w_scale.astype(jnp.float32))
    if has_scale:
        in_specs.append(
            pl.BlockSpec((block_m, 1), lambda m, n, k, be, ba: (m, 0)))
        operands.append(row_scale.reshape(capacity, 1).astype(jnp.float32))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n_m, n_n, n_k),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((block_m, block_n),
                               lambda m, n, k, be, ba: (m, n)),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
    )

    def kernel(be, ba, *refs):
        # refs: x, w, [w_scale], [row_scale], out, acc
        it = iter(refs)
        x_ref, w_ref = next(it), next(it)
        ws_ref = next(it) if quant else None
        scale_ref = next(it) if has_scale else None
        out_ref, acc_ref = next(it), next(it)
        _kernel(be, ba, x_ref, w_ref, ws_ref, scale_ref, out_ref, acc_ref,
                n_k=n_k, has_scale=has_scale, w_format=w_format)

    out_dtype = out_dtype or x.dtype
    fn = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((capacity, N), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )
    return fn(block_expert, block_active, *operands)
