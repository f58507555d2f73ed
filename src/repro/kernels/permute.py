"""Token permutation kernel — the paper's §3.5 gather, TPU form.

Scatter-to-expert-contiguous is expressed as its inverse gather: padded
row r of the output is row ``src_tok[r]`` of ``x``.  The grid is
(d-tiles, row-blocks) with the row-blocks innermost, so each (T, block_d)
column tile of ``x`` is DMA'd into VMEM once and stays resident while
every (block_rows, block_d) output tile is assembled from it: one
dynamic-offset row load per padded row, its index read from the
scalar-prefetched ``src_tok`` table.  Output tiles are whole row blocks,
so no block is smaller than a TPU tile.  A sub-32-bit dtype packs
several rows into one sublane, where a single row cannot be addressed,
so its tiles are staged through float32 VMEM copies.  Padding rows
(src_tok == -1) are zero-filled so downstream grouped GEMMs see exact
zeros.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(src_ref, x_ref, out_ref, *stage, block_rows: int):
    i = pl.program_id(1)
    if stage:
        x32_ref, o32_ref = stage

        @pl.when(i == 0)
        def _stage():
            x32_ref[...] = x_ref[...].astype(jnp.float32)
    else:
        x32_ref, o32_ref = x_ref, out_ref

    def gather(r):
        s = src_ref[i * block_rows + r]
        row = x32_ref[pl.ds(jnp.maximum(s, 0), 1), :]
        o32_ref[pl.ds(r, 1), :] = jnp.where(s >= 0, row, 0.0)

    u = 8 if block_rows % 8 == 0 else 1     # Mosaic unrolls fully or not

    def rows(g, carry):
        for r in range(u):
            gather(g * u + r)
        return carry

    jax.lax.fori_loop(0, block_rows // u, rows, 0)
    if stage:
        out_ref[...] = o32_ref[...].astype(out_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("block_d", "block_rows", "interpret"))
def permute(x: jnp.ndarray, src_tok: jnp.ndarray, *, block_d: int = 0,
            block_rows: int = 0, interpret: bool = False) -> jnp.ndarray:
    """x: (T, d); src_tok: (capacity,) int32 (-1 = padding) -> (capacity, d).

    ``block_d`` divides d (a (T, block_d) tile of x is VMEM-resident);
    ``block_rows`` divides capacity (rows assembled per grid step)."""
    T, d = x.shape
    capacity = src_tok.shape[0]
    block_d = block_d or d
    block_rows = block_rows or capacity
    assert d % block_d == 0 and capacity % block_rows == 0, (
        (T, d, capacity), (block_d, block_rows))
    stage = ([] if x.dtype == jnp.float32 else
             [pltpu.VMEM((T, block_d), jnp.float32),
              pltpu.VMEM((block_rows, block_d), jnp.float32)])

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(d // block_d, capacity // block_rows),
        in_specs=[pl.BlockSpec((T, block_d), lambda j, i, src: (0, j))],
        out_specs=pl.BlockSpec((block_rows, block_d),
                               lambda j, i, src: (i, j)),
        scratch_shapes=stage,
    )
    fn = pl.pallas_call(
        functools.partial(_kernel, block_rows=block_rows),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((capacity, d), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )
    return fn(src_tok.astype(jnp.int32), x)
