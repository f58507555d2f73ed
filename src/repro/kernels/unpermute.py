"""Unpermute + weighted-combine kernel — the paper's §3.5 inverse scatter.

For token t, the k expert outputs live at padded rows ``pos[t, :]``, and
``out[t] = sum_c w[t, c] * y[pos[t, c]]``.  The kernel computes the same
sum from the other side, as a scatter-add: it streams ``y`` once in
whole (block_rows, block_d) tiles and adds each row r, scaled by its
combine weight ``w_row[r]``, into the row ``src_tok[r]`` of a float32
VMEM accumulator that holds every token's (T, block_d) output tile (the
paper's FP32 accumulation).  The grid is (d-tiles, row-blocks) with the
row-blocks innermost; the accumulator is written out once, after the
last row block.  Row indices are read from the scalar-prefetched
``src_tok`` table, so no block is smaller than a TPU tile and no row of
``y`` is fetched on its own.

``w_row`` is ``weights`` scattered to the padded rows through ``pos``
(O(T*k) scalars).  A row that no ``pos`` entry names gets weight 0, so
it adds nothing, exactly as in the gather form.  When the combine
weights were already folded into the down projection's epilogue (our
beyond-paper fusion), the caller passes ``weights=None`` and every named
row gets weight 1.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(src_ref, y_ref, w_ref, out_ref, acc_ref, ys_ref, *,
            block_rows: int):
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    ys_ref[...] = y_ref[...].astype(jnp.float32) * w_ref[...]

    def scatter(r):
        t = src_ref[i * block_rows + r]

        @pl.when(t >= 0)
        def _add():
            acc_ref[pl.ds(t, 1), :] += ys_ref[pl.ds(r, 1), :]

    u = 8 if block_rows % 8 == 0 else 1     # Mosaic unrolls fully or not

    def rows(g, carry):
        for r in range(u):
            scatter(g * u + r)
        return carry

    jax.lax.fori_loop(0, block_rows // u, rows, 0)

    @pl.when(i == pl.num_programs(1) - 1)
    def _write():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("block_d", "block_rows", "interpret"))
def unpermute(y: jnp.ndarray, src_tok: jnp.ndarray, pos: jnp.ndarray,
              weights: jnp.ndarray | None, *, block_d: int = 0,
              block_rows: int = 0, interpret: bool = False) -> jnp.ndarray:
    """y: (capacity, d); src_tok: (capacity,) source token of each padded
    row (-1 = padding); pos: (T, k) padded row of expanded token (t, c);
    weights: (T, k) combine weights or None (already folded) -> (T, d).

    ``block_d`` divides d (a (T, block_d) accumulator is VMEM-resident);
    ``block_rows`` divides capacity (rows streamed per grid step)."""
    capacity, d = y.shape
    T, k = pos.shape
    block_d = block_d or d
    block_rows = block_rows or capacity
    assert d % block_d == 0 and capacity % block_rows == 0, (
        (T, d, capacity), (block_d, block_rows))
    w = (jnp.ones((T, k), jnp.float32) if weights is None
         else weights.astype(jnp.float32))
    w_row = jnp.zeros((capacity,), jnp.float32).at[pos.reshape(-1)].add(
        w.reshape(-1), mode="drop")

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(d // block_d, capacity // block_rows),
        in_specs=[
            pl.BlockSpec((block_rows, block_d), lambda j, i, src: (i, j)),
            pl.BlockSpec((block_rows, 1), lambda j, i, src: (i, 0)),
        ],
        out_specs=pl.BlockSpec((T, block_d), lambda j, i, src: (0, j)),
        scratch_shapes=[pltpu.VMEM((T, block_d), jnp.float32),
                        pltpu.VMEM((block_rows, block_d), jnp.float32)],
    )
    fn = pl.pallas_call(
        functools.partial(_kernel, block_rows=block_rows),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((T, d), y.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )
    return fn(src_tok.astype(jnp.int32), y, w_row.reshape(capacity, 1))
