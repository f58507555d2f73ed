"""DeepSeek-V2 Multi-head Latent Attention (MLA).

Train/prefill: decompress the latent to per-head K/V and run chunked flash
attention (standard).  Decode: the cache stores only the compressed latent
``c_kv`` (kv_lora dims) plus the shared rope key — the whole point of MLA —
and attention runs in the *absorbed* form (q projected into latent space;
per-head K/V never materialized), chunked over the cached sequence.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import MLAConfig
from repro.models.attention import (flash_attention, gather_block_kv,
                                    scatter_block_rows, scatter_decode_row)
from repro.models.blocks import apply_norm, dense_init, init_norm, rope


def init_mla(key, d_model: int, n_heads: int, mla: MLAConfig,
             dtype=jnp.float32):
    ks = jax.random.split(key, 6)
    dn, dr, dv = mla.qk_nope_head_dim, mla.qk_rope_head_dim, mla.v_head_dim
    return {
        "wq_a": dense_init(ks[0], (d_model, mla.q_lora_rank), dtype=dtype),
        "q_norm": init_norm(mla.q_lora_rank, "rmsnorm"),
        "wq_b": dense_init(ks[1], (mla.q_lora_rank, n_heads * (dn + dr)),
                           dtype=dtype),
        "wkv_a": dense_init(ks[2], (d_model, mla.kv_lora_rank + dr),
                            dtype=dtype),
        "kv_norm": init_norm(mla.kv_lora_rank, "rmsnorm"),
        "wkv_b": dense_init(ks[3], (mla.kv_lora_rank, n_heads * (dn + dv)),
                            dtype=dtype),
        "wo": dense_init(ks[4], (n_heads * dv, d_model), dtype=dtype),
    }


def _project_q(p, x, n_heads: int, mla: MLAConfig, positions):
    dn, dr = mla.qk_nope_head_dim, mla.qk_rope_head_dim
    B, S, _ = x.shape
    cq = apply_norm(p["q_norm"], jnp.dot(x, p["wq_a"].astype(x.dtype)),
                    "rmsnorm")
    q = jnp.dot(cq, p["wq_b"].astype(x.dtype)).reshape(B, S, n_heads, dn + dr)
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    q_rope = rope(q_rope, positions, 10_000.0)
    return q_nope, q_rope


def _latent(p, x, mla: MLAConfig, positions):
    """x -> (c_kv normalized (B,S,r), k_rope (B,S,dr))."""
    dr = mla.qk_rope_head_dim
    ckv_full = jnp.dot(x, p["wkv_a"].astype(x.dtype))
    c_kv = apply_norm(p["kv_norm"], ckv_full[..., :mla.kv_lora_rank],
                      "rmsnorm")
    k_rope = rope(ckv_full[..., mla.kv_lora_rank:][:, :, None, :],
                  positions, 10_000.0)[:, :, 0, :]
    return c_kv, k_rope


def mla_block(p, x: jnp.ndarray, *, n_heads: int, mla: MLAConfig,
              positions: jnp.ndarray, cache: Optional[dict] = None,
              cache_pos=None, block_tables=None, paged_fused: bool = False,
              q_chunk: int = 512, kv_chunk: int = 512):
    """Returns (out, new_cache). Cache: {"ckv": (B,S,r), "kr": (B,S,dr)};
    with ``block_tables`` (B, nb) the cache leaves are paged block pools
    (n_blocks, block_size, ...) written block-granular and read through a
    per-row gather — the latent cache pages exactly like attention K/V.
    ``paged_fused`` runs absorbed decode through the fused Pallas
    paged-attention kernel instead: the latent pools are scored in place
    (q_eff/ckv + q_rope/kr as a two-operand score, ckv doubling as the
    value), so neither the gathered latent view nor the concatenated key
    ever materializes."""
    dn, dr, dv = mla.qk_nope_head_dim, mla.qk_rope_head_dim, mla.v_head_dim
    B, S, _ = x.shape
    q_nope, q_rope = _project_q(p, x, n_heads, mla, positions)
    c_kv, k_rope = _latent(p, x, mla, positions)

    if cache is None:
        # ---- train/prefill: decompress, chunked flash over full seq ----
        kv = jnp.dot(c_kv, p["wkv_b"].astype(x.dtype)).reshape(
            B, S, n_heads, dn + dv)
        k_nope, v = kv[..., :dn], kv[..., dn:]
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope[:, :, None, :],
                                      (B, S, n_heads, dr))], axis=-1)
        q = jnp.concatenate([q_nope, q_rope], axis=-1)
        out = flash_attention(q, k, v, causal=True,
                              q_chunk=q_chunk, kv_chunk=kv_chunk)
        out = out.reshape(B, S, n_heads * dv)
        return jnp.dot(out, p["wo"].astype(x.dtype)), None

    # ---- decode: absorbed attention over the compressed cache ----
    # (scatter_decode_row handles scalar and (B,) per-slot positions)
    idx = cache_pos
    if block_tables is not None:
        new_ckv = scatter_block_rows(cache["ckv"], c_kv, block_tables, idx)
        new_kr = scatter_block_rows(cache["kr"], k_rope, block_tables, idx)
        new_cache = {"ckv": new_ckv, "kr": new_kr}
        if paged_fused:
            out = _mla_fused_paged_decode(
                p, q_nope, q_rope, new_ckv, new_kr, block_tables, idx,
                n_heads=n_heads, mla=mla)
            out = out.reshape(B, S, n_heads * dv)
            return jnp.dot(out, p["wo"].astype(x.dtype)), new_cache
        ckv_view = gather_block_kv(new_ckv, block_tables)
        kr_view = gather_block_kv(new_kr, block_tables)
    else:
        new_ckv = scatter_decode_row(cache["ckv"], c_kv, idx)
        new_kr = scatter_decode_row(cache["kr"], k_rope, idx)
        new_cache = {"ckv": new_ckv, "kr": new_kr}
        ckv_view, kr_view = new_ckv, new_kr

    out = mla_absorbed_decode(
        p, q_nope, q_rope, ckv_view.astype(x.dtype), kr_view.astype(x.dtype),
        n_heads=n_heads, mla=mla, kv_limit=idx, kv_chunk=kv_chunk)
    out = out.reshape(B, S, n_heads * dv)
    return jnp.dot(out, p["wo"].astype(x.dtype)), new_cache


def _mla_fused_paged_decode(p, q_nope, q_rope, ckv_pool, kr_pool, tables,
                            kv_limit, *, n_heads: int, mla: MLAConfig):
    """Absorbed MLA decode straight off the latent block pools.

    Mirrors ``mla_absorbed_decode`` over ``gather_block_kv`` views term
    for term — same absorbed q construction, same two-step scale
    compensation (pre-scale by ((r+dr)/(dn+dr))^0.5 then the kernel's
    (r+dr)^-0.5, in the same dtype and order) — but the scores run inside
    the fused Pallas kernel with ckv/kr as two scalar-prefetch-indexed
    score operands and ckv as the value.  Returns (B, 1, H, dv)."""
    from repro.kernels.ops import paged_decode_attention
    dn, dr, dv = mla.qk_nope_head_dim, mla.qk_rope_head_dim, mla.v_head_dim
    r = mla.kv_lora_rank
    B = q_nope.shape[0]
    wkv_b = p["wkv_b"].astype(q_nope.dtype).reshape(r, n_heads, dn + dv)
    w_k, w_v = wkv_b[..., :dn], wkv_b[..., dn:]
    q_eff = jnp.einsum("bthd,rhd->bthr", q_nope, w_k)         # (B,1,H,r)
    comp = jnp.asarray(((r + dr) ** 0.5) / ((dn + dr) ** 0.5),
                       q_eff.dtype)
    # (B, 1, H, *) doubles as the kernel's (B, Hkv=1, G=H, *) layout
    q1 = q_eff * comp
    q2 = q_rope * comp
    ckv4 = ckv_pool[:, :, None, :]                            # Hkv=1 axis
    kr4 = kr_pool[:, :, None, :]
    ctx = paged_decode_attention(
        q1, ckv4, ckv4, tables, kv_limit, scale=(r + dr) ** -0.5,
        q2=q2, k2_pool=kr4)                                   # (B,1,H,r)
    return jnp.einsum("bthr,rhd->bthd", ctx.astype(q_nope.dtype), w_v)


def mla_absorbed_decode(p, q_nope, q_rope, ckv, kr, *, n_heads: int,
                        mla: MLAConfig, kv_limit, kv_chunk: int = 2048,
                        kv_offset: int = 0, return_stats: bool = False):
    """Absorbed-form attention: score = q_nope W_k^T c + q_rope k_rope;
    context stays in latent space until the final W_v projection.

    q_*: (B, 1, H, dn|dr); ckv: (B, S, r); kr: (B, S, dr).
    """
    dn, dr, dv = mla.qk_nope_head_dim, mla.qk_rope_head_dim, mla.v_head_dim
    r = mla.kv_lora_rank
    B, S = ckv.shape[:2]
    wkv_b = p["wkv_b"].astype(q_nope.dtype).reshape(r, n_heads, dn + dv)
    w_k, w_v = wkv_b[..., :dn], wkv_b[..., dn:]
    # absorb: q_eff (B,1,H,r) so scores need only the latent cache
    q_eff = jnp.einsum("bthd,rhd->bthr", q_nope, w_k)
    # keys: latent ckv (acts per-head-identically) + shared rope key.
    # Treat (r + dr) as the effective qk head dim, Hkv=1 GQA group.
    q_cat = jnp.concatenate([q_eff, q_rope], axis=-1)         # (B,1,H,r+dr)
    k_cat = jnp.concatenate([ckv, kr], axis=-1)[:, :, None, :]  # (B,S,1,r+dr)
    # flash_attention scales by D^-0.5 of its qk dim; MLA scales by the
    # *decompressed* head dim (dn + dr). Pre-scale to compensate.
    q_cat = q_cat * jnp.asarray(
        ((r + dr) ** 0.5) / ((dn + dr) ** 0.5), q_cat.dtype)
    stats = flash_attention(
        q_cat, k_cat, ckv[:, :, None, :], causal=False, kv_limit=kv_limit,
        kv_offset=kv_offset, q_chunk=1, kv_chunk=kv_chunk,
        return_stats=return_stats)
    if return_stats:
        return stats, w_v
    ctx = stats                                               # (B,1,H,r)
    return jnp.einsum("bthr,rhd->bthd", ctx, w_v)             # (B,1,H,dv)
