"""Public jit'd wrappers for the Pallas kernels.

Handles block-size selection (MXU-aligned divisors), automatic
``interpret=True`` off-TPU (this container validates kernels on CPU in
interpret mode; the compiled target is TPU v5e), adapts the
schedule-carrying call signatures to the BlockSchedule tuple, and splits
scheme-tagged ``QuantTensor`` expert weights into the kernels' compressed
payload + per-channel-scale operands (in-kernel dequant, DESIGN.md §8).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.quantization import QuantTensor, get_scheme
from repro.scheduling import BlockSchedule
from repro.kernels import fused_gate_up as _fgu
from repro.kernels import grouped_gemm as _gg
from repro.kernels import paged_attention as _paged
from repro.kernels import permute as _perm
from repro.kernels import router_topk as _router
from repro.kernels import unpermute as _unperm


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _interp(flag: bool | None) -> bool:
    return (not on_tpu()) if flag is None else flag


_block_warned: set = set()


def _warn_block_once(fn: str, n: int, target: int, got: int) -> None:
    key = (fn, n, target, got)
    if key not in _block_warned:
        _block_warned.add(key)
        import warnings
        warnings.warn(
            f"{fn}: no well-aligned divisor of {n} <= {target}; falling "
            f"back to block size {got} (correct but slow — pad the dim "
            "toward a multiple of 128 for MXU-shaped tiles)",
            RuntimeWarning, stacklevel=3)


def pick_block(n: int, target: int, align: int = 128) -> int:
    """Largest TRUE divisor of n that is <= target, preferring MXU
    alignment (multiples of ``align``, then of 8, then any).

    Total: every n >= 1 yields a valid divisor — awkward dims (primes,
    odd N/K) fall back to the largest unaligned divisor and warn once
    per (n, target) instead of tripping the kernels' divisibility
    asserts downstream."""
    if n < 1:
        raise ValueError(f"pick_block: non-positive dim {n}")
    target = max(1, min(n, target))
    for a in (align, 8, 1):
        if n % a:
            continue
        b = (target // a) * a
        while b >= a:
            if n % b == 0:
                if b < min(8, target) and n >= 8:
                    # degenerate: a big dim with only tiny divisors
                    _warn_block_once("pick_block", n, target, b)
                return b
            b -= a
    # unreachable (a=1 always succeeds at b=1), kept as a total fallback
    _warn_block_once("pick_block", n, target, 1)
    return 1


def _weight_operands(w):
    """Split an expert-weight stack into kernel operands.

    Dense array -> (w, None, "dense", (K, N)); QuantTensor -> (payload,
    (E, N) f32 channel scales, scheme kernel_format, logical (K, N)).
    """
    if isinstance(w, QuantTensor):
        if w.meta:
            # padded layouts (int4 odd-K) have no in-kernel dequant path:
            # fall back to the dense operand (edge case; the paper
            # configs' K are all even)
            w = w.materialize()
            return w, None, "dense", tuple(w.shape[-2:])
        sch = get_scheme(w.scheme)
        K, N = w.shape[-2:]
        return w.q, sch.channel_scales(w), sch.kernel_format, (K, N)
    return w, None, "dense", tuple(w.shape[-2:])


def _pick_block_k(K: int, target: int, w_format: str) -> int:
    """Like pick_block, but an int4-packed payload DMAs block_k//2 rows,
    so the logical block must stay even."""
    bk = pick_block(K, target)
    if w_format == "int4":
        while bk > 2 and (bk % 2 or K % bk):
            bk -= 1                    # K is even (asserted at pack time)
        if bk % 2 or K % bk:
            # total fallback: K even (pack-time invariant) => 2 divides K
            bk = 2
            if K % bk:
                raise ValueError(
                    f"int4 payload needs an even K divisor; K={K} is odd")
            _warn_block_once("_pick_block_k", K, target, bk)
    return bk


def _tuned_blocks(kernel: str, *, M: int, K: int, N: int, E: int,
                  dtype, fmt: str, block_n: int, block_k: int):
    """Trace-time tune-cache consult (DESIGN.md §12): swap the hard-coded
    block targets for this shape key's swept winner when one exists.

    Shapes are concrete Python ints during tracing, so the lookup runs
    once per compiled shape and costs nothing per step.  A miss keeps the
    caller's defaults — an absent/stale cache degrades, never breaks."""
    from repro.tuning import lookup_block_sizes
    rec = lookup_block_sizes(kernel, M=M, K=K, N=N, E=E,
                             dtype=jnp.dtype(dtype).name,
                             scheme=fmt, executor="pallas")
    if rec is None:
        return block_n, block_k
    return rec["block_n"], rec["block_k"]


# ----------------------------------------------------------------------
def router_topk(logits: jnp.ndarray, *, top_k: int, gating: str = "softmax",
                norm_topk: bool = False, routed_scale: float = 1.0,
                block_t: int = 256, interpret: bool | None = None):
    T = logits.shape[0]
    return _router.router_topk(
        logits, top_k=top_k, gating=gating, norm_topk=norm_topk,
        routed_scale=routed_scale, block_t=pick_block(T, block_t, align=8),
        interpret=_interp(interpret))


# A (rows, block_d) float32 tile of x (permute) or of the combine
# accumulator (unpermute) stays VMEM-resident for a whole pass over the
# padded rows; this bounds it well inside the default scoped-VMEM limit.
_RESIDENT_BYTES = 2 << 20
_ROW_BLOCK = 128                # padded rows moved per grid step


def _resident_block_d(rows: int, d: int, target: int) -> int:
    fit = max(128, _RESIDENT_BYTES // (4 * rows) // 128 * 128)
    return pick_block(d, min(target, fit))


def permute(x: jnp.ndarray, sched: BlockSchedule, *, block_d: int = 2048,
            interpret: bool | None = None) -> jnp.ndarray:
    return _perm.permute(
        x, sched.src_tok,
        block_d=_resident_block_d(x.shape[0], x.shape[-1], block_d),
        block_rows=pick_block(sched.capacity, _ROW_BLOCK, align=8),
        interpret=_interp(interpret))


def unpermute(y: jnp.ndarray, sched: BlockSchedule,
              weights: jnp.ndarray | None, *, block_d: int = 2048,
              interpret: bool | None = None) -> jnp.ndarray:
    return _unperm.unpermute(
        y, sched.src_tok, sched.pos, weights,
        block_d=_resident_block_d(sched.pos.shape[0], y.shape[-1], block_d),
        block_rows=pick_block(sched.capacity, _ROW_BLOCK, align=8),
        interpret=_interp(interpret))


def grouped_gemm(x: jnp.ndarray, w, sched: BlockSchedule,
                 row_scale: jnp.ndarray | None = None, *,
                 block_n: int = 512, block_k: int = 512,
                 autotune: bool = False,
                 interpret: bool | None = None) -> jnp.ndarray:
    """``w``: (E, K, N) array or a QuantTensor (in-kernel dequant).
    ``autotune`` consults the persistent tune cache for this shape key's
    swept (block_n, block_k) winner before the divisor snap."""
    wq, ws, fmt, (K, N) = _weight_operands(w)
    E = wq.shape[0]
    if autotune:
        block_n, block_k = _tuned_blocks(
            "grouped_gemm", M=x.shape[0], K=K, N=N, E=E, dtype=x.dtype,
            fmt=fmt, block_n=block_n, block_k=block_k)
    return _gg.grouped_gemm(
        x, wq, sched.block_expert, sched.block_active, row_scale, ws,
        block_m=sched.block_m, w_format=fmt,
        block_n=pick_block(N, block_n),
        block_k=_pick_block_k(K, block_k, fmt),
        interpret=_interp(interpret))


def fused_gate_up(x: jnp.ndarray, w_gate, w_up,
                  sched: BlockSchedule, *, block_n: int = 512,
                  block_k: int = 512, autotune: bool = False,
                  interpret: bool | None = None) -> jnp.ndarray:
    """``w_gate``/``w_up``: (E, K, F) arrays or QuantTensors under ONE
    scheme (in-kernel dequant).  ``autotune`` as in ``grouped_gemm``."""
    wgq, wsg, fmt_g, (K, F) = _weight_operands(w_gate)
    wuq, wsu, fmt_u, _ = _weight_operands(w_up)
    assert fmt_g == fmt_u, (fmt_g, fmt_u)
    if autotune:
        block_n, block_k = _tuned_blocks(
            "fused_gate_up", M=x.shape[0], K=K, N=F, E=wgq.shape[0],
            dtype=x.dtype, fmt=fmt_g, block_n=block_n, block_k=block_k)
    return _fgu.fused_gate_up(
        x, wgq, wuq, sched.block_expert, sched.block_active, wsg, wsu,
        block_m=sched.block_m, w_format=fmt_g,
        block_n=pick_block(F, block_n),
        block_k=_pick_block_k(K, block_k, fmt_g),
        interpret=_interp(interpret))


def grouped_wgrad(x: jnp.ndarray, dy: jnp.ndarray, sched: BlockSchedule,
                  n_experts: int, *, block_n: int = 512, block_k: int = 512,
                  interpret: bool | None = None) -> jnp.ndarray:
    """Training-backward tgmm: dW[e] = x_e^T dy_e over the padded layout.
    Experts that received zero tokens never get their block flushed by the
    kernel, so they are explicitly zeroed here."""
    from repro.kernels import grouped_wgrad as _wg
    K, N = x.shape[-1], dy.shape[-1]
    dw = _wg.grouped_wgrad(
        x, dy, sched.block_expert, sched.block_active,
        n_experts=n_experts, block_m=sched.block_m,
        block_k=pick_block(K, block_k), block_n=pick_block(N, block_n),
        interpret=_interp(interpret))
    return jnp.where((sched.counts > 0)[:, None, None], dw, 0.0)


def paged_decode_attention(q, k_pool, v_pool, tables, kv_limit, *,
                           scale: float, logit_softcap=None, q2=None,
                           k2_pool=None):
    """The fused paged decode kernel (kernels/paged_attention.py).

    GSPMD cannot partition a Mosaic kernel, so under an active mesh (the
    EP serving step, whose attention runs replicated) every device runs
    it whole on replicated operands inside a ``shard_map``."""
    def attend(q, k_pool, v_pool, tables, kv_limit, q2, k2_pool):
        return _paged.paged_decode_attention(
            q, k_pool, v_pool, tables, kv_limit, scale=scale,
            logit_softcap=logit_softcap, q2=q2, k2_pool=k2_pool,
            interpret=_interp(None))
    mesh = jax.sharding.get_abstract_mesh()
    if not mesh.empty:
        attend = jax.shard_map(attend, mesh=mesh, in_specs=P(),
                               out_specs=P(), check_vma=False)
    return attend(q, k_pool, v_pool, tables, kv_limit, q2, k2_pool)
