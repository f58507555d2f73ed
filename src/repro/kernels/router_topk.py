"""Fused gating + iterative top-k router kernel — the paper's §3.4.

One pass over a (BLOCK_T, E) tile of router logits:
  * manual numerically-stable softmax (subtract row max — the paper notes
    Triton's builtin skips this; jnp.softmax is stable but we keep the manual
    form so the kernel matches the paper's computation step-for-step), or
    sigmoid gating (DeepSeek-style) with optional top-k renormalization;
  * top-k by iterative argmax; selected entries are masked to -inf (the
    paper masks to -1.0 which suffices for scores in [0,1]; -inf is the
    strict generalization) so they can never be re-selected — the 0.0-mask
    failure mode at E=256 described in the paper cannot occur;
  * argmax is expressed as max + where + min-index so tie-breaking (lowest
    expert index) is explicit and identical on every backend.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _kernel(logits_ref, w_ref, i_ref, *, top_k: int, gating: str,
            norm_topk: bool, routed_scale: float):
    x = logits_ref[...].astype(jnp.float32)             # (BT, E)
    bt, E = x.shape
    if gating == "softmax":
        m = jnp.max(x, axis=-1, keepdims=True)          # manual stable softmax
        e = jnp.exp(x - m)
        scores = e / jnp.sum(e, axis=-1, keepdims=True)
    else:  # sigmoid
        scores = jax.nn.sigmoid(x)

    col = jax.lax.broadcasted_iota(jnp.int32, (bt, E), 1)
    masked = scores
    for j in range(top_k):                              # static unroll, k <= 8
        mx = jnp.max(masked, axis=-1, keepdims=True)
        is_max = masked == mx
        idx = jnp.min(jnp.where(is_max, col, E), axis=-1)      # lowest index
        w = jnp.max(jnp.where(col == idx[:, None], scores, -jnp.inf), axis=-1)
        i_ref[:, j] = idx.astype(jnp.int32)
        w_ref[:, j] = w
        masked = jnp.where(col == idx[:, None], -jnp.inf, masked)

    if norm_topk:
        all_w = w_ref[...]
        w_ref[...] = all_w / (jnp.sum(all_w, axis=-1, keepdims=True) + 1e-20)
    if routed_scale != 1.0:
        w_ref[...] = w_ref[...] * routed_scale


@functools.partial(
    jax.jit,
    static_argnames=("top_k", "gating", "norm_topk", "routed_scale",
                     "block_t", "interpret"))
def router_topk(logits: jnp.ndarray, *, top_k: int, gating: str = "softmax",
                norm_topk: bool = False, routed_scale: float = 1.0,
                block_t: int = 256, interpret: bool = False):
    """logits: (T, E) -> (weights (T, top_k) f32, indices (T, top_k) i32)."""
    T, E = logits.shape
    block_t = min(block_t, T)
    assert T % block_t == 0, f"T={T} not divisible by block_t={block_t}"

    fn = pl.pallas_call(
        functools.partial(_kernel, top_k=top_k, gating=gating,
                          norm_topk=norm_topk, routed_scale=routed_scale),
        grid=(T // block_t,),
        in_specs=[pl.BlockSpec((block_t, E), lambda t: (t, 0))],
        out_specs=[pl.BlockSpec((block_t, top_k), lambda t: (t, 0)),
                   pl.BlockSpec((block_t, top_k), lambda t: (t, 0))],
        out_shape=[jax.ShapeDtypeStruct((T, top_k), jnp.float32),
                   jax.ShapeDtypeStruct((T, top_k), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )
    return tuple(fn(logits))
