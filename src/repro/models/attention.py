"""Attention substrate: chunked ("flash") attention in pure JAX.

No T^2 tensor is ever materialized: the computation is a scan over query
chunks with an inner scan over KV chunks carrying running (max, denom, acc)
statistics — the standard online-softmax formulation.  This is what the
full-scale dry-run lowers (32k prefill would otherwise need multi-GB score
buffers), and it is exact (tests compare against naive attention).

Features: GQA (grouped KV heads), causal masks, sliding windows (gemma2
local layers — banded so FLOPs stay O(S*W)), attention-logit softcap,
bidirectional (encoder) mode, cross-attention, decode with a KV-position
limit, and ``return_stats`` for the cross-device flash-decode LSE combine
(distributed/decode.py)."""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.blocks import dense_init, rope

NEG_INF = -1e30  # finite -inf stand-in: keeps exp()/where() NaN-free


def init_attn(key, d_model: int, n_heads: int, n_kv_heads: int, head_dim: int,
              bias: bool, dtype=jnp.float32, v_head_dim: int = 0):
    vd = v_head_dim or head_dim
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], (d_model, n_heads * head_dim), dtype=dtype),
        "wk": dense_init(ks[1], (d_model, n_kv_heads * head_dim), dtype=dtype),
        "wv": dense_init(ks[2], (d_model, n_kv_heads * vd), dtype=dtype),
        "wo": dense_init(ks[3], (n_heads * vd, d_model), dtype=dtype),
    }
    if bias:
        p["bq"] = jnp.zeros((n_heads * head_dim,), dtype)
        p["bk"] = jnp.zeros((n_kv_heads * head_dim,), dtype)
        p["bv"] = jnp.zeros((n_kv_heads * vd,), dtype)
    return p


def project_qkv(p, x: jnp.ndarray, xkv: jnp.ndarray, n_heads: int,
                n_kv_heads: int, head_dim: int, v_head_dim: int = 0):
    """x: (B, S, d) queries source; xkv: (B, Skv, d) key/value source."""
    vd = v_head_dim or head_dim
    dt = x.dtype
    q = jnp.dot(x, p["wq"].astype(dt))
    k = jnp.dot(xkv, p["wk"].astype(dt))
    v = jnp.dot(xkv, p["wv"].astype(dt))
    if "bq" in p:
        q, k, v = q + p["bq"].astype(dt), k + p["bk"].astype(dt), v + p["bv"].astype(dt)
    B, S, _ = x.shape
    Skv = xkv.shape[1]
    return (q.reshape(B, S, n_heads, head_dim),
            k.reshape(B, Skv, n_kv_heads, head_dim),
            v.reshape(B, Skv, n_kv_heads, vd))


def _pick_chunk(n: int, target: int) -> int:
    c = min(n, target)
    while n % c:
        c -= 1
    return c


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "logit_softcap", "q_chunk",
                     "kv_chunk", "return_stats"))
def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    causal: bool = True, window: Optional[int] = None,
                    logit_softcap: Optional[float] = None,
                    q_offset=0, kv_offset=0,
                    kv_limit: Optional[jnp.ndarray] = None,
                    q_chunk: int = 512, kv_chunk: int = 512,
                    return_stats: bool = False):
    """q: (B, Sq, Hq, D); k: (B, Skv, Hkv, D); v: (B, Skv, Hkv, Dv).

    q_offset/kv_offset: absolute position of the first query/key (CP shards
    pass their global offsets).  kv_limit: inclusive max attended key
    position, scalar or (B,) (decode).  Returns (B, Sq, Hq, Dv); with
    return_stats, returns (unnormalized_acc, sumexp l, rowmax m) for LSE
    combination across KV shards.
    """
    B, Sq, Hq, D = q.shape
    _, Skv, Hkv, Dv = v.shape[0], k.shape[1], k.shape[2], v.shape[-1]
    G = Hq // Hkv
    qc = _pick_chunk(Sq, q_chunk)
    kc = _pick_chunk(Skv, kv_chunk)
    nq, nk = Sq // qc, Skv // kc

    # keep q/k/v in their storage dtype; per-chunk MXU einsums accumulate in
    # fp32 via preferred_element_type. Materializing fp32 copies up front
    # costs 2x HBM on the (replicated-under-CP) K/V — measured at ~6 GB per
    # device per layer for MLA at 32k (EXPERIMENTS.md §Perf iteration 3).
    scale = jnp.asarray(D ** -0.5, q.dtype)
    qr = q.reshape(B, nq, qc, Hkv, G, D) * scale
    kr = k.reshape(B, nk, kc, Hkv, D)
    vr = v.reshape(B, nk, kc, Hkv, Dv)

    if kv_limit is not None:
        kv_lim = jnp.broadcast_to(jnp.asarray(kv_limit), (B,)).astype(jnp.int32)
    else:
        kv_lim = None

    def one_q_chunk(qi, qb):                     # qb: (B, qc, Hkv, G, D)
        qpos = q_offset + qi * qc + jnp.arange(qc)          # (qc,)

        def kv_step(carry, inp):
            m, l, acc = carry
            ki, kb, vb = inp                      # kb: (B, kc, Hkv, D)
            s = jnp.einsum("bqhgd,bkhd->bhgqk", qb, kb,     # (B,Hkv,G,qc,kc)
                           preferred_element_type=jnp.float32)
            if logit_softcap is not None:
                s = logit_softcap * jnp.tanh(s / logit_softcap)
            kpos = kv_offset + ki * kc + jnp.arange(kc)     # (kc,)
            ok = jnp.ones((qc, kc), bool)
            if causal:
                ok &= kpos[None, :] <= qpos[:, None]
            if window is not None:
                ok &= kpos[None, :] > qpos[:, None] - window
            mask = ok[None, None, None]
            if kv_lim is not None:
                mask = mask & (kpos[None, None, None, None, :]
                               <= kv_lim[:, None, None, None, None])
            s = jnp.where(mask, s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            p = jnp.where(mask, p, 0.0)
            corr = jnp.exp(m - m_new)
            l_new = corr * l + jnp.sum(p, axis=-1)
            # P is cast to the value dtype for the MXU (standard TPU flash
            # practice); accumulation stays fp32.
            acc_new = (corr[..., None] * acc
                       + jnp.einsum("bhgqk,bkhd->bhgqd", p.astype(vb.dtype),
                                    vb, preferred_element_type=jnp.float32))
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, Hkv, G, qc), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, Hkv, G, qc), jnp.float32)
        a0 = jnp.zeros((B, Hkv, G, qc, Dv), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(
            kv_step, (m0, l0, a0),
            (jnp.arange(nk), jnp.moveaxis(kr, 1, 0), jnp.moveaxis(vr, 1, 0)))
        return m, l, acc

    ms, ls, accs = jax.lax.map(
        lambda t: one_q_chunk(t[0], t[1]),
        (jnp.arange(nq), jnp.moveaxis(qr, 1, 0)))
    # (nq, B, Hkv, G, qc[, Dv]) -> (B, Sq, Hq[, Dv])
    def _restore(x, last=()):
        x = jnp.moveaxis(x, 0, 3)                 # (B,Hkv,G,nq,qc,...)
        return x.reshape((B, Hkv, G, Sq) + last)
    m = _restore(ms)
    l = _restore(ls)
    acc = _restore(accs, (Dv,))
    if return_stats:
        return acc, l, m                          # (B,Hkv,G,Sq,Dv),(B,Hkv,G,Sq)
    out = jnp.where(l[..., None] > 0, acc / jnp.maximum(l[..., None], 1e-30), 0)
    out = jnp.moveaxis(out, 3, 1).reshape(B, Sq, Hq, Dv)
    return out.astype(q.dtype)


def combine_stats(acc, l, m, axis_name: str):
    """LSE-combine partial attention stats across a mesh axis (flash-decode).

    Each rank holds (acc, l, m) for its KV shard; the result equals attention
    over the full KV. Used by distributed/decode.py inside shard_map."""
    m_g = jax.lax.pmax(m, axis_name)
    corr = jnp.exp(m - m_g)
    l_g = jax.lax.psum(l * corr, axis_name)
    acc_g = jax.lax.psum(acc * corr[..., None], axis_name)
    out = jnp.where(l_g[..., None] > 0,
                    acc_g / jnp.maximum(l_g[..., None], 1e-30), 0)
    return out


def naive_attention(q, k, v, *, causal=True, window=None, logit_softcap=None,
                    kv_limit=None):
    """O(S^2)-memory oracle for tests."""
    B, Sq, Hq, D = q.shape
    Hkv = k.shape[2]
    G = Hq // Hkv
    qf = q.astype(jnp.float32) * D ** -0.5
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qf.reshape(B, Sq, Hkv, G, D),
                   k.astype(jnp.float32))
    if logit_softcap is not None:
        s = logit_softcap * jnp.tanh(s / logit_softcap)
    qpos, kpos = jnp.arange(Sq), jnp.arange(k.shape[1])
    ok = jnp.ones((Sq, k.shape[1]), bool)
    if causal:
        ok &= kpos[None] <= qpos[:, None]
    if window is not None:
        ok &= kpos[None] > qpos[:, None] - window
    mask = ok[None, None, None]
    if kv_limit is not None:
        lim = jnp.broadcast_to(jnp.asarray(kv_limit), (B,)).astype(jnp.int32)
        mask = mask & (kpos[None, None, None, None, :]
                       <= lim[:, None, None, None, None])
    s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(mask, p, 0.0)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", p, v.astype(jnp.float32))
    return out.reshape(B, Sq, Hq, -1).astype(q.dtype)


def attention_block(p, x: jnp.ndarray, *, n_heads: int, n_kv_heads: int,
                    head_dim: int, causal: bool, use_rope: bool,
                    rope_theta: float, positions: jnp.ndarray,
                    window: Optional[int] = None,
                    logit_softcap: Optional[float] = None,
                    xkv: Optional[jnp.ndarray] = None,
                    kv_positions: Optional[jnp.ndarray] = None,
                    cache: Optional[dict] = None,
                    cache_pos: Optional[jnp.ndarray] = None,
                    block_tables: Optional[jnp.ndarray] = None,
                    paged_fused: bool = False,
                    q_chunk: int = 512, kv_chunk: int = 512):
    """Full attention sub-block: project -> rope -> (cache update) -> flash
    -> output projection.  Returns (out, new_cache).

    Decode: ``cache_pos`` is a scalar (all rows write/attend at the same
    position) or a (B,) vector — the batched-serving path, where each cache
    row carries its own sequence position (``scatter_decode_row`` + per-row
    ``kv_limit`` mask).  With ``block_tables`` (B, nb) the cache is a paged
    block POOL instead of per-row buffers: writes scatter block-granular
    (``scatter_block_rows``) and reads gather each row's logical view
    through its table (``gather_block_kv``) — same math, paged storage.
    ``paged_fused`` replaces that gather + flash with the fused Pallas
    paged-attention kernel (kernels/paged_attention.py): online softmax
    walks the block-table-indexed pool tiles directly, so the gathered
    view never materializes.  ``gather_block_kv`` remains the
    differential oracle (token-identical greedy decode, asserted in
    tests/test_paged_attention.py)."""
    from repro.distributed.ctx import constrain
    source_kv = x if xkv is None else xkv
    q, k, v = project_qkv(p, x, source_kv, n_heads, n_kv_heads, head_dim)
    if use_rope:
        q = rope(q, positions, rope_theta)
        kp = positions if kv_positions is None else kv_positions
        if xkv is None:                       # self-attn: keys share positions
            k = rope(k, kp, rope_theta)
    q = constrain("q_seq", constrain("qkv", q))
    k = constrain("kv_full", constrain("qkv", k))
    v = constrain("kv_full", constrain("qkv", v))
    new_cache = None
    kv_limit = None
    kv_off = 0
    if cache is not None:
        # decode: write this step's k/v at cache_pos, attend to <= cache_pos
        idx = cache_pos
        if block_tables is not None:
            new_k = scatter_block_rows(cache["k"], k, block_tables, idx)
            new_v = scatter_block_rows(cache["v"], v, block_tables, idx)
            new_cache = {"k": new_k, "v": new_v}
            if paged_fused:
                # fused path: attend straight off the pool.  The decode
                # flash call below runs with qpos=0 (Sq=1), which makes
                # the window term inert — the fused call mirrors that
                # exactly (window omitted) so both paths stay bitwise
                # companions.
                from repro.kernels.ops import paged_decode_attention
                B = q.shape[0]
                G = n_heads // n_kv_heads
                qf = q[:, 0].reshape(B, n_kv_heads, G, head_dim)
                out = paged_decode_attention(
                    qf, new_k, new_v, block_tables, idx,
                    scale=head_dim ** -0.5, logit_softcap=logit_softcap)
                out = out.reshape(B, 1, n_heads, -1).astype(q.dtype)
                out = out.reshape(B, 1, -1)
                return jnp.dot(out, p["wo"].astype(x.dtype)), new_cache
            k = gather_block_kv(new_k, block_tables).astype(q.dtype)
            v = gather_block_kv(new_v, block_tables).astype(q.dtype)
        else:
            new_k = scatter_decode_row(cache["k"], k, idx)
            new_v = scatter_decode_row(cache["v"], v, idx)
            new_cache = {"k": new_k, "v": new_v}
            k, v = new_k.astype(q.dtype), new_v.astype(q.dtype)
        kv_limit = idx
        causal = False
    out = flash_attention(q, k, v, causal=causal, window=window,
                          logit_softcap=logit_softcap,
                          kv_limit=kv_limit, kv_offset=kv_off,
                          q_chunk=q_chunk, kv_chunk=kv_chunk)
    B, S = x.shape[:2]
    out = out.reshape(B, S, -1)
    return jnp.dot(out, p["wo"].astype(x.dtype)), new_cache


def gather_block_kv(pool: jnp.ndarray, tables: jnp.ndarray) -> jnp.ndarray:
    """Paged attention READ: reassemble each row's logically-contiguous
    KV view from the global block pool.

    pool: (n_blocks, block_size, ...); tables: (B, nb) int32 physical block
    ids in logical order.  Returns (B, nb * block_size, ...) — row b's view
    holds its sequence positions in order, so downstream flash attention
    (kv_limit masking, rope'd keys, windows) is unchanged: paging is
    invisible past the gather.  Unallocated table entries may point at
    arbitrary blocks; their logical positions lie beyond the row's
    ``kv_limit`` and are masked."""
    g = pool[tables]                        # (B, nb, bs, ...)
    return g.reshape(g.shape[0], g.shape[1] * g.shape[2], *g.shape[3:])


def scatter_block_rows(pool: jnp.ndarray, val: jnp.ndarray,
                       tables: jnp.ndarray, pos) -> jnp.ndarray:
    """Paged attention WRITE: the block-granular sibling of
    ``scatter_decode_row``.

    pool: (n_blocks, block_size, ...); val: (B, 1, ...); tables: (B, nb);
    pos: (B,) logical positions.  Token b lands at physical
    ``(tables[b, pos[b] // block_size], pos[b] % block_size)``.  Rank-
    agnostic (attention K/V and the MLA latent cache share it).  The
    engine guarantees the (block, offset) pairs of one step are pairwise
    distinct: decode tokens occupy different slots and a prefill chunk's
    tokens occupy consecutive positions of one slot — so the point
    scatter's unordered updates never collide.  A position past the
    row's table (nb * block_size) is DROPPED, matching the contiguous
    cache's out-of-bounds scatter at the capacity edge (the engine
    retires such rows on the same step)."""
    bs = pool.shape[1]
    nb = tables.shape[1]
    pos = jnp.asarray(pos)
    logical = pos // bs
    blk = jnp.take_along_axis(tables, jnp.clip(logical, 0, nb - 1)[:, None],
                              axis=1)[:, 0]
    # rows past the table get an out-of-range physical id; mode="drop"
    # discards them (they hold no block to write)
    blk = jnp.where(logical < nb, blk, pool.shape[0])
    return pool.at[blk, pos % bs].set(val[:, 0].astype(pool.dtype),
                                      mode="drop")


def scatter_decode_row(cache: jnp.ndarray, val: jnp.ndarray, pos):
    """Write one decode step's row into a cache along the sequence axis.

    cache: (B, S, ...); val: (B, 1, ...); pos: scalar (shared position) or
    (B,) per-row positions (batched serving).  Rank-agnostic — the same
    primitive serves attention K/V (B, S, H, D) and the MLA latent cache
    (B, S, r).  The vector case is a point scatter, not a dense one-hot
    blend: per step it writes O(B * row) instead of reading and blending
    the whole O(B * S * row) cache."""
    pos = jnp.asarray(pos)
    if pos.ndim == 0:
        return jax.lax.dynamic_update_slice_in_dim(
            cache, val.astype(cache.dtype), pos, axis=1)
    B = cache.shape[0]
    return cache.at[jnp.arange(B), pos].set(val[:, 0].astype(cache.dtype))
