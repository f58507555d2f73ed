"""Fused gate+up grouped GEMM with in-register SiLU — the paper's §3.3.

Both SwiGLU projections are computed from the SAME input tile per grid step:
the A block is DMA'd HBM->VMEM once and feeds two MXU matmuls whose fp32
accumulators live in VMEM scratch.  The SiLU(gate) * up epilogue runs in
vector registers before a single bf16 copy-out, so the ``gate_out`` and
``up_out`` intermediates never exist in HBM.

HBM traffic (T tokens, K = d_model, F = d_ffn, bf16):
  unfused: A read twice (2*T*K*2B) + gate_out/up_out written + read back
           (4*T*F*2B) + intermediate written (T*F*2B)   = 10TF + 4TK bytes*
  fused:   A read once (T*K*2B) + intermediate written (T*F*2B) = 2TF + 2TK
  (*weight traffic identical in both; the paper counts a subset of these
  terms and lands on ~35% — our accounting in benchmarks/stage_roofline.py
  reports both conventions.)

Quantized weights: like grouped_gemm.py, ``w_format`` turns both weight
operands into compressed payloads with per-channel ``w*_scale`` operands,
dequantized per DMA'd block in VREGs right before the MXU issues
(DESIGN.md §8).  ``w_format="dense"`` is the original kernel (bitwise).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.grouped_gemm import dequant_weight_block


def _kernel(block_expert_ref, block_active_ref,       # scalar prefetch
            x_ref, wg_ref, wu_ref, wsg_ref, wsu_ref,  # inputs (ws* opt.)
            out_ref,                                  # output
            acc_g_ref, acc_u_ref,                     # scratch
            *, n_k: int, w_format: str):
    m, _, k = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    active = block_active_ref[m] == 1

    @pl.when(k == 0)
    def _zero():
        acc_g_ref[...] = jnp.zeros_like(acc_g_ref)
        acc_u_ref[...] = jnp.zeros_like(acc_u_ref)

    @pl.when(active)
    def _accum():
        x = x_ref[...]                                # one VMEM A-tile ...
        wg = dequant_weight_block(
            wg_ref[0], None if wsg_ref is None else wsg_ref[...],
            w_format, x.dtype)
        wu = dequant_weight_block(
            wu_ref[0], None if wsu_ref is None else wsu_ref[...],
            w_format, x.dtype)
        acc_g_ref[...] += jnp.dot(x, wg,              # ... two MXU issues
                                  preferred_element_type=jnp.float32)
        acc_u_ref[...] += jnp.dot(x, wu,
                                  preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _epilogue():
        g = acc_g_ref[...]
        h = g * jax.nn.sigmoid(g) * acc_u_ref[...]    # SiLU(g) * u, in VREGs
        out_ref[...] = h.astype(out_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("block_m", "block_n", "block_k", "interpret",
                     "out_dtype", "w_format"))
def fused_gate_up(x: jnp.ndarray, w_gate: jnp.ndarray, w_up: jnp.ndarray,
                  block_expert: jnp.ndarray, block_active: jnp.ndarray,
                  wg_scale: jnp.ndarray | None = None,
                  wu_scale: jnp.ndarray | None = None, *,
                  block_m: int, block_n: int, block_k: int,
                  w_format: str = "dense",
                  interpret: bool = False, out_dtype=None) -> jnp.ndarray:
    """x: (capacity, K); w_gate/w_up: (E, K, F) dense or the scheme's
    packed payload; w*_scale: (E, F) f32 per-channel scales (quant only)
    -> silu(x@wg)*(x@wu): (capacity, F).  ``block_k`` is in LOGICAL K."""
    capacity, K = x.shape
    F = w_gate.shape[-1]
    pack = 2 if w_format == "int4" else 1
    assert w_up.shape == w_gate.shape
    assert w_gate.shape[1] * pack == K, (w_gate.shape, K, w_format)
    assert (wg_scale is not None) == (w_format != "dense"), w_format
    assert capacity % block_m == 0 and K % block_k == 0 and F % block_n == 0, (
        f"shape {(capacity, K, F)} not divisible by blocks "
        f"{(block_m, block_k, block_n)}")
    assert block_k % pack == 0, (block_k, w_format)
    n_m, n_n, n_k = capacity // block_m, F // block_n, K // block_k
    quant = w_format != "dense"

    w_spec = pl.BlockSpec((1, block_k // pack, block_n),
                          lambda m, n, k, be, ba: (be[m], k, n))
    in_specs = [
        pl.BlockSpec((block_m, block_k), lambda m, n, k, be, ba: (m, k)),
        w_spec, w_spec,
    ]
    operands = [x, w_gate, w_up]
    if quant:
        s_spec = pl.BlockSpec((1, block_n),
                              lambda m, n, k, be, ba: (be[m], n))
        in_specs += [s_spec, s_spec]
        operands += [wg_scale.astype(jnp.float32),
                     wu_scale.astype(jnp.float32)]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n_m, n_n, n_k),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((block_m, block_n),
                               lambda m, n, k, be, ba: (m, n)),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32),
                        pltpu.VMEM((block_m, block_n), jnp.float32)],
    )

    def kernel(be, ba, *refs):
        # refs: x, wg, wu, [wsg, wsu], out, acc_g, acc_u
        it = iter(refs)
        x_ref, wg_ref, wu_ref = next(it), next(it), next(it)
        wsg_ref = next(it) if quant else None
        wsu_ref = next(it) if quant else None
        out_ref, acc_g_ref, acc_u_ref = next(it), next(it), next(it)
        _kernel(be, ba, x_ref, wg_ref, wu_ref, wsg_ref, wsu_ref,
                out_ref, acc_g_ref, acc_u_ref, n_k=n_k, w_format=w_format)

    fn = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((capacity, F), out_dtype or x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )
    return fn(block_expert, block_active, *operands)
