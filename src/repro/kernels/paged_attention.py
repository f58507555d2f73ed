"""Fused Pallas paged-attention decode kernel (DESIGN.md §12).

The paged serve path (PR 5) reads KV through ``gather_block_kv``: an XLA
gather that MATERIALIZES each row's logically-contiguous (B, nb*bs, H, D)
view in HBM before flash attention re-reads it — per decoded token, the
whole attended cache is written once and read once more than necessary.
This kernel runs flash-style online softmax directly over the block pool:
the grid walks each row's block table, the scalar-prefetched table drives
the KV ``BlockSpec.index_map`` (the same SMEM-lookup trick the grouped
GEMM uses for expert weights), and each (bs, D) KV tile is DMA'd from the
pool exactly once.  The gathered view never exists.

Masking mirrors ``models/attention.flash_attention``: an inclusive
per-row ``kv_limit``, optional causal / sliding-window terms against a
per-row query position, optional logit softcap, fp32 accumulation with
the probability matrix cast to the value dtype before its MXU issue, and
the same ``max(l, 1e-30)`` guarded divide — so greedy argmax tokens are
identical to the gather path (asserted token-for-token in
tests/test_paged_attention.py; ``gather_block_kv`` stays as the
differential oracle).

The MLA latent path fuses too: scores there are ``q_eff @ ckv^T +
q_rope @ kr^T`` with the latent ``ckv`` doubling as the value — passed as
a second (q2, k2_pool) score operand, so the per-row latent view is never
concatenated or materialized either.

Off-TPU this runs in interpret mode (the container validates on CPU; the
compiled target is TPU v5e).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30          # finite -inf stand-in (matches attention.py)


def _kernel(tables_ref, lim_ref, qpos_ref,        # scalar prefetch
            q_ref, k_ref, v_ref, q2_ref, k2_ref,  # inputs (q2/k2 optional)
            o_ref,                                # output
            m_ref, l_ref, acc_ref,                # scratch
            *, n_blocks_per_row: int, block_size: int,
            causal: bool, window: Optional[int],
            logit_softcap: Optional[float]):
    b, j = pl.program_id(0), pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0]                               # (G, D) pre-scaled
    k = k_ref[0]                                  # (bs, D)
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)   # (G, bs)
    if q2_ref is not None:
        s += jnp.dot(q2_ref[0, 0], k2_ref[0].T,
                     preferred_element_type=jnp.float32)
    if logit_softcap is not None:
        s = logit_softcap * jnp.tanh(s / logit_softcap)

    kpos = j * block_size + jax.lax.broadcasted_iota(
        jnp.int32, (1, block_size), 1)            # (1, bs)
    ok = kpos <= lim_ref[b]
    if causal:
        ok &= kpos <= qpos_ref[b]
    if window is not None:
        ok &= kpos > qpos_ref[b] - window
    s = jnp.where(ok, s, NEG_INF)

    m_prev, l_prev = m_ref[...], l_ref[...]       # (G, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    p = jnp.where(ok, p, 0.0)
    corr = jnp.exp(m_prev - m_new)
    v = v_ref[0]                                  # (bs, Dv)
    acc_ref[...] = corr * acc_ref[...] + jnp.dot(
        p.astype(v.dtype), v, preferred_element_type=jnp.float32)
    m_ref[...] = m_new
    l_ref[...] = corr * l_prev + jnp.sum(p, axis=-1, keepdims=True)

    @pl.when(j == n_blocks_per_row - 1)
    def _flush():
        l = l_ref[...]
        out = jnp.where(l > 0, acc_ref[...] / jnp.maximum(l, 1e-30), 0.0)
        o_ref[0, 0] = out.astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "logit_softcap", "scale",
                     "interpret"))
def paged_decode_attention(q: jnp.ndarray, k_pool: jnp.ndarray,
                           v_pool: jnp.ndarray, tables: jnp.ndarray,
                           kv_limit: jnp.ndarray, *,
                           scale: Optional[float] = None,
                           q_pos: Optional[jnp.ndarray] = None,
                           causal: bool = False,
                           window: Optional[int] = None,
                           logit_softcap: Optional[float] = None,
                           q2: Optional[jnp.ndarray] = None,
                           k2_pool: Optional[jnp.ndarray] = None,
                           interpret: bool = False) -> jnp.ndarray:
    """One decode step of attention straight off the paged block pool.

    q: (B, Hkv, G, D) — row b is one decode token, GQA grouped;
    k_pool: (n_blocks, bs, Hkv, D); v_pool: (n_blocks, bs, Hkv, Dv);
    tables: (B, nb) int32 physical block ids in logical order;
    kv_limit: (B,) or scalar inclusive max attended position;
    q_pos: (B,) query positions — required for causal/window masks;
    q2/k2_pool: optional second score operand (MLA: q_eff/ckv + q_rope/kr
    with v_pool == the ckv pool), same layout with its own depth D2;
    scale: applied to q (and q2) in the query dtype, default D**-0.5.

    Returns (B, Hkv, G, Dv) in q.dtype.  Unallocated table entries may
    point at arbitrary pool blocks; their logical positions lie beyond
    ``kv_limit`` and are masked — identical semantics to
    ``gather_block_kv`` + ``flash_attention``.
    """
    B, Hkv, G, D = q.shape
    n_blocks, bs = k_pool.shape[0], k_pool.shape[1]
    Dv = v_pool.shape[-1]
    nb = tables.shape[1]
    assert tables.shape == (B, nb), (tables.shape, B)
    assert k_pool.shape[2] == Hkv and v_pool.shape[2] == Hkv
    if scale is None:
        scale = D ** -0.5
    q = q * jnp.asarray(scale, q.dtype)
    two = q2 is not None
    if two:
        assert k2_pool is not None
        q2 = q2 * jnp.asarray(scale, q2.dtype)
        D2 = q2.shape[-1]
        assert k2_pool.shape == (n_blocks, bs, Hkv, D2), k2_pool.shape

    tf = tables.reshape(-1).astype(jnp.int32)                 # (B*nb,)
    lim = jnp.broadcast_to(jnp.asarray(kv_limit), (B,)).astype(jnp.int32)
    qp = (jnp.zeros((B,), jnp.int32) if q_pos is None
          else jnp.broadcast_to(jnp.asarray(q_pos), (B,)).astype(jnp.int32))
    if (causal or window is not None) and q_pos is None:
        raise ValueError("causal/window masks need q_pos (per-row query "
                         "positions)")

    def kv_spec(depth):
        # the pool is viewed as (n_blocks, bs, Hkv * depth) — a free
        # reshape — so head h's (bs, depth) tile is a lane-aligned column
        # block (depth % 128 == 0, or one head spanning the whole row) and
        # no size-1 head block sits in the tiled minor dims
        return pl.BlockSpec((1, bs, depth),
                            lambda b, h, j, t, l, p: (t[b * nb + j], 0, h))

    def pool(x):
        return x.reshape(n_blocks, bs, -1)

    in_specs = [
        pl.BlockSpec((1, 1, G, D), lambda b, h, j, t, l, p: (b, h, 0, 0)),
        kv_spec(D), kv_spec(Dv),
    ]
    operands = [q, pool(k_pool), pool(v_pool)]
    if two:
        in_specs += [
            pl.BlockSpec((1, 1, G, D2),
                         lambda b, h, j, t, l, p: (b, h, 0, 0)),
            kv_spec(D2),
        ]
        operands += [q2, pool(k2_pool)]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, Hkv, nb),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, G, Dv),
                               lambda b, h, j, t, l, p: (b, h, 0, 0)),
        scratch_shapes=[pltpu.VMEM((G, 1), jnp.float32),
                        pltpu.VMEM((G, 1), jnp.float32),
                        pltpu.VMEM((G, Dv), jnp.float32)],
    )

    def kernel(t, l, p, *refs):
        it = iter(refs)
        q_ref, k_ref, v_ref = next(it), next(it), next(it)
        q2_ref = next(it) if two else None
        k2_ref = next(it) if two else None
        o_ref, m_ref, l_ref, acc_ref = next(it), next(it), next(it), next(it)
        _kernel(t, l, p, q_ref, k_ref, v_ref, q2_ref, k2_ref,
                o_ref, m_ref, l_ref, acc_ref,
                n_blocks_per_row=nb, block_size=bs, causal=causal,
                window=window, logit_softcap=logit_softcap)

    fn = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, Dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )
    return fn(tf, lim, qp, *operands)
