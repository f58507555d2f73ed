"""Chip smoke test: the serving main path on a TPU, end to end.

One process, phases in order, nothing caught; any failed phase exits
non-zero and prints no result line.

  (a) device  — fail unless jax's first device is a TPU;
  (b) engine  — build the serving engine through the launcher's own code
                (``repro.launch.serve.build_engine``): Pallas executor,
                fused paged decode (``--paged-attn auto``), the ``dynamic``
                schedule policy, 4 slots over a paged KV cache;
  (c) serve   — 4 seeded requests (prompts of 32-200 tokens over the full
                vocab, 16 new tokens each); every request must finish;
  (d) compare — prefill logits of the same prompts under the ``pallas``,
                ``xla`` and float32 ``dense`` executors (the last under
                ``jax.default_matmul_precision("highest")``; pallas also
                at that precision), against the stated tolerances;
  (e) figures — compile time, serve time, tokens served, peak device
                bytes, and the count of compiled Mosaic kernels
                (``tpu_custom_call``) in the serving step — smoke-test
                figures, not benchmark numbers.

The model is moonshot-v1-16b-a3b (Moonlight-16B-A3B) at its published
widths, cut in depth only: 3 of 48 layers (the leading dense layer and 2
MoE layers), random weights from a seed, the repository's default fp32.

``--chips 4`` runs only the expert-parallel serving path instead (the
launcher's ``--distributed`` mode: a 4-device EP mesh with 16 of the 64
experts on each device, the default decode layout, ``--ep-overlap`` on)
and compares its prefill logits with the one-device logits computed in
the same process afterwards, both at full matmul precision.

The last line of standard output is one JSON object:
``{"ok": true, "device": {"platform", "kind", "count"}}``.

Usage (from the root of a checkout):
    python chip_smoke.py
    python chip_smoke.py --chips 4
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

REPO = pathlib.Path(__file__).resolve().parent
ARCH = "moonshot-v1-16b-a3b"
LAYERS = 3                    # 1 dense + 2 MoE layers of the published 48
SEED = 0
N_REQUESTS, MAX_NEW = 4, 16
PROMPT_LEN = (32, 200)        # inclusive range of prompt lengths
CAPACITY = 256                # per-slot KV capacity; also the padded
                              # prefill length of the logits comparison

# Logits comparison.  For each prompt position p the error is
#     e_p = max_v |a[p, v] - b[p, v]| / max_v |b[p, v]|
# (v over the whole vocab).
#
# Full precision: both sides under jax.default_matmul_precision("highest")
# (the Pallas kernels' own float32 dots are full precision either way).
# They then differ only in float32 summation order, about 1e-6 of a
# logit; EXACT_TOL is 1000x that, and a wrong row, expert or weight
# moves a position by 1e-2 or more.  A token whose 6th- and 7th-best
# router scores lie within that rounding may still pick another expert
# in the two runs and move by about a fifth — rare at this precision,
# so EXACT_SHARE of the positions, not all, must be within EXACT_TOL.
EXACT_TOL, EXACT_SHARE = 1e-3, 0.99
# Served precision: jax's default, under which every fp32 matmul that
# XLA runs is one bf16 pass, with a relative rounding of about 2**-8.
# About 20 such matmuls chain through 3 layers and the vocab projection,
# so a position moves by about sqrt(20) * 2**-8 ~= 2% against the full
# precision reference, and near-tied router scores now swap experts at
# a noticeable share of positions.  Only the median is bounded, at 2.5x
# that estimate: half the positions may not drift further.
SERVED_MEDIAN = 0.05
# The serving step of this model holds at least 7 compiled kernels: the 5
# of the scanned MoE layer (router_topk, permute, fused_gate_up,
# grouped_gemm, unpermute) and the fused paged decode attention of the
# dense layer and of the scanned layer.
MIN_KERNELS = 7


def fail(msg: str) -> None:
    print(f"chip_smoke: FAILED: {msg}", file=sys.stderr)
    sys.exit(1)


def phase(name: str) -> None:
    print(f"\n== {name}", flush=True)


def launcher_args(*extra: str):
    """The serving launcher's own flags and defaults (``--autotune`` is
    off: only committed files decide what compiles)."""
    from repro.launch import serve
    return serve.build_parser().parse_args([
        "--arch", ARCH, "--executor", "pallas", "--paged-attn", "auto",
        "--schedule-policy", "dynamic", "--slots", str(N_REQUESTS),
        "--capacity", str(CAPACITY), "--requests", str(N_REQUESTS),
        "--max-new", str(MAX_NEW), *extra])


def model_config(args):
    import math

    import jax

    from repro.configs import get_config
    from repro.models import init_params
    full = get_config(args.arch)
    cfg = full.replace(n_layers=LAYERS)
    shapes = jax.eval_shape(lambda: init_params(cfg, jax.random.key(0)))
    n = sum(math.prod(l.shape) for l in jax.tree.leaves(shapes))
    nbytes = sum(l.size * l.dtype.itemsize for l in jax.tree.leaves(shapes))
    m = cfg.moe
    print(f"model: {full.name} cut in depth only, {LAYERS} of "
          f"{full.n_layers} layers ({m.first_dense_layers} dense + "
          f"{LAYERS - m.first_dense_layers} MoE); published widths kept: "
          f"d_model {cfg.d_model}, {cfg.n_heads} heads x {cfg.head_dim} "
          f"(kv {cfg.n_kv_heads}), dense FFN {m.d_ff_dense}, "
          f"{m.n_experts} routed experts x {m.d_ff_expert} top-{m.top_k} "
          f"+ {m.n_shared_experts} shared, {m.gating} gating "
          f"routed_scale {m.routed_scale}, vocab {cfg.vocab_size}; "
          f"{n / 1e9:.3f} B params, {nbytes / 1e9:.2f} GB "
          f"({shapes['embed'].dtype})")
    return cfg


def make_prompts(cfg):
    import numpy as np
    rng = np.random.default_rng(SEED)
    lo, hi = PROMPT_LEN
    return [rng.integers(0, cfg.vocab_size, int(L)).astype(np.int32)
            for L in rng.integers(lo, hi + 1, N_REQUESTS)]


class CompileClock:
    """Seconds jax spends tracing, lowering and compiling (its own
    ``/jax/core/compile/*`` duration events)."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event.startswith("/jax/core/compile/"):
            self.seconds += duration


def serve_requests(args, engine, prompts):
    """Phase (c) through the launcher's run loop; returns (requests,
    wall seconds)."""
    from repro.serve.engine import Request
    reqs = [Request(rid=i, prompt=p, max_new=args.max_new)
            for i, p in enumerate(prompts)]
    t0 = time.perf_counter()
    if args.distributed:
        from repro.serve.distributed import DistributedServeLoop
        DistributedServeLoop(engine, admission=args.admission).run(
            reqs, max_steps=args.max_steps)
    else:
        engine.run(reqs, max_steps=args.max_steps)
    wall = time.perf_counter() - t0
    for r in reqs:
        print(f"  req {r.rid}: prompt {len(r.prompt)} tokens -> "
              f"{len(r.out)} new {r.out[:4]}...")
        if not r.done or len(r.out) != args.max_new:
            fail(f"request {r.rid} did not finish "
                 f"({len(r.out)}/{args.max_new} tokens)")
    return reqs, wall


def step_kernel_count(engine) -> int:
    """``tpu_custom_call`` ops in the compiled serving step at a decode
    shape (one token per slot)."""
    import jax
    import jax.numpy as jnp
    n = engine.slots
    nb = engine.kv.table_rows([0]).shape[1]
    i32 = jnp.int32
    rows = jax.ShapeDtypeStruct((n,), i32)
    hlo = engine._pstep.lower(   # the engine's one jitted paged step
        engine.params, engine.kv.pools,
        {"tokens": jax.ShapeDtypeStruct((n, 1), i32)}, rows,
        jax.ShapeDtypeStruct((n, nb), i32), rows, rows, rows,
    ).compile().as_text()
    return hlo.count("tpu_custom_call")


def prefill_logits(params, cfg, rc, prompts):
    """(B, CAPACITY, V) float32 logits of every prompt position from one
    full forward over the right-padded prompts (causal attention: the
    padding never reaches a real position; the drop-free ``dynamic``
    policy routes each token on its own)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models.blocks import softcap
    from repro.models.lm import _head_matrix, forward
    toks = np.zeros((len(prompts), CAPACITY), np.int32)
    for b, p in enumerate(prompts):
        toks[b, :len(p)] = p

    @jax.jit
    def run(params, toks):
        h, _, _ = forward(params, cfg, rc, {"tokens": toks}, mode="train")
        logits = jnp.dot(h, _head_matrix(params, cfg).astype(h.dtype))
        return softcap(logits.astype(jnp.float32), cfg.final_logit_softcap)
    return np.asarray(jax.device_get(run(params, jnp.asarray(toks))))


def compare(name, a, b, prompts, *, exact: bool) -> bool:
    """Per-position error of ``a`` against ``b`` over the real prompt
    positions, judged by the full-precision rule (``exact``) or the
    served-precision one; True iff within it."""
    import numpy as np
    errs = np.concatenate([
        np.max(np.abs(a[i, :len(p)] - b[i, :len(p)]), -1)
        / np.max(np.abs(b[i, :len(p)]), -1)
        for i, p in enumerate(prompts)])
    maxabs = max(float(np.max(np.abs(a[i, :len(p)] - b[i, :len(p)])))
                 for i, p in enumerate(prompts))
    med, share = float(np.median(errs)), float(np.mean(errs <= EXACT_TOL))
    rule = (f"need {EXACT_SHARE} within {EXACT_TOL}" if exact
            else f"need median <= {SERVED_MEDIAN}")
    print(f"  {name}: max |diff| {maxabs:.6g}; per-position error median "
          f"{med:.6g} max {errs.max():.6g}; {share:.4f} of {errs.size} "
          f"positions within {EXACT_TOL} ({rule})")
    return share >= EXACT_SHARE if exact else med <= SERVED_MEDIAN


def memory_line(devices) -> str:
    return ", ".join(
        f"{d.id}: peak {d.memory_stats()['peak_bytes_in_use'] / 1e9:.3f} GB"
        f" in use {d.memory_stats()['bytes_in_use'] / 1e9:.3f} GB"
        for d in devices)


def one_chip(devices) -> None:
    import jax

    from repro.launch import serve
    from repro.obs import NOOP

    phase("(b) engine")
    args = launcher_args()
    cfg = model_config(args)
    clock = CompileClock()
    engine = serve.build_engine(args, cfg, obs=NOOP)
    rc = engine.rc
    print(f"  executor {rc.executor}, paged_attn {rc.paged_attn} (fused), "
          f"schedule {rc.schedule_policy}, {engine.slots} slots, paged KV "
          f"{engine.kv.n_blocks} blocks x {engine.kv.block_size} tokens")

    phase("(c) serve")
    prompts = make_prompts(cfg)
    reqs, wall = serve_requests(args, engine, prompts)
    served = sum(len(r.out) for r in reqs)
    compile_s = clock.seconds
    kernels = step_kernel_count(engine)

    phase("(d) compare prefill logits")
    with jax.default_matmul_precision("highest"):
        ref = prefill_logits(engine.params, cfg,
                             rc._replace(executor="dense"), prompts)
        pallas_full = prefill_logits(engine.params, cfg, rc, prompts)
    xla = prefill_logits(engine.params, cfg, rc._replace(executor="xla"),
                         prompts)
    pallas = prefill_logits(engine.params, cfg, rc, prompts)
    agree = [
        compare("pallas vs dense fp32 reference, full precision",
                pallas_full, ref, prompts, exact=True),
        compare("pallas vs xla, served precision", pallas, xla, prompts,
                exact=False),
        compare("pallas, served precision, vs dense fp32 reference",
                pallas, ref, prompts, exact=False),
        compare("xla, served precision, vs dense fp32 reference",
                xla, ref, prompts, exact=False)]

    phase("(e) smoke-test figures (not benchmark numbers)")
    print(f"  compile (jax trace+lower+compile events during serving): "
          f"{compile_s:.3f} s")
    print(f"  serve wall time, compilation included: {wall:.3f} s for "
          f"{served} tokens served ({len(reqs)} requests)")
    print(f"  device memory: {memory_line(devices)}")
    print(f"  tpu_custom_call in the compiled serving step: {kernels}")
    if not all(agree):
        fail("prefill logits disagree beyond the stated tolerances")
    if kernels < MIN_KERNELS:
        fail(f"serving step holds {kernels} compiled kernels, expected "
             f">= {MIN_KERNELS}: some kernel did not compile for the TPU")


def four_chips(devices) -> None:
    import gc

    import jax

    from repro.launch import serve
    from repro.models import init_params
    from repro.obs import NOOP

    phase("(b) EP engine on 4 devices")
    args = launcher_args("--distributed", "--ep-overlap")
    cfg = model_config(args)
    prompts = make_prompts(cfg)
    with jax.set_mesh(serve.ep_mesh(args)):
        engine = serve.build_engine(args, cfg, obs=NOOP)
        w = engine.params["body"]["b0"]["moe"]["w_gate"]
        local = w.sharding.shard_shape(w.shape)
        print(f"  routed experts per device: {local[1]} of {w.shape[1]} "
              f"(w_gate shard {local} of {w.shape})")
        if local[1] * len(devices) != w.shape[1]:
            fail("routed experts are not split over the EP mesh")
        print(f"  after placement: {memory_line(devices)}")

        phase("(c) serve on the EP mesh")
        reqs, wall = serve_requests(args, engine, prompts)
        kernels = step_kernel_count(engine)
        phase("(d) EP prefill logits")
        with jax.default_matmul_precision("highest"):
            ep = prefill_logits(engine.params, cfg, engine.rc, prompts)
    print(f"  per-device memory after the EP phases: {memory_line(devices)}")
    print(f"  serve wall time, compilation included: {wall:.3f} s for "
          f"{sum(len(r.out) for r in reqs)} tokens; tpu_custom_call in "
          f"the compiled EP serving step: {kernels}")
    rc1 = engine.rc._replace(ep=False, ep_overlap=False)
    del engine, w
    gc.collect()

    phase("(d) one-device logits, same process")
    params = jax.jit(lambda: init_params(cfg, jax.random.key(0)))()
    with jax.default_matmul_precision("highest"):
        one = prefill_logits(params, cfg, rc1, prompts)
    if not compare("EP (4 devices) vs one device, full precision", ep, one,
                   prompts, exact=True):
        fail("EP logits disagree beyond the stated tolerance")
    if kernels < MIN_KERNELS:
        fail(f"EP serving step holds {kernels} compiled kernels, expected "
             f">= {MIN_KERNELS}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the one-chip serving path (default); 4: only "
                         "the expert-parallel path over 4 chips")
    chips = ap.parse_args().chips
    if not (REPO / "src" / "repro").is_dir():
        fail(f"no repository around {__file__}: run from a checkout")
    sys.path.insert(0, str(REPO / "src"))

    import jax

    from repro.launch import use_compile_cache
    use_compile_cache()

    phase("(a) device")
    devices = jax.devices()
    d0 = devices[0]
    print(f"  platform {d0.platform}, kind {d0.device_kind}, "
          f"count {len(devices)}")
    if d0.platform != "tpu":
        fail(f"no TPU: jax found {d0.platform} devices")
    if len(devices) < chips:
        fail(f"--chips {chips} needs {chips} devices, found {len(devices)}")
    used = devices[:chips]
    (four_chips if chips == 4 else one_chip)(used)
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
