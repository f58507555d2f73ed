"""Decode throughput of the batched serving engine: active-slot count x
schedule policy, plus a mixed prefill/decode shared-prefix workload
comparing the paged cache (prefix caching + chunked prefill, DESIGN.md §9)
against the contiguous pre-paging engine.

The paper's throughput claim is that MoE wins come from batching tokens
into one fused dispatch; at serve time the decode batch IS the set of
active slots, so this sweep measures exactly that lever: every step is one
jitted forward over the (slots, capacity) cache — one DispatchPlan per MoE
layer covering all slots — and tokens/sec is slots * steps / wall.  More
active slots amortize both the per-step dispatch overhead and the expert
weight traffic (the dominant decode cost), so decode throughput should
rise with slot count; the fixed-vs-dynamic policy axis shows what schedule
construction costs on realistic decode batches.

Steady-state methodology: all slots are admitted up front (max_new large
enough that nothing retires inside the timed window), two warmup steps
absorb compilation, then ``--steps`` lock-step decodes are timed.

Records go to results/serve/<arch><suffix>.json (CSV on stdout follows
benchmarks/common emit conventions).

    PYTHONPATH=src python -m benchmarks.serving_throughput [--smoke]
"""
from __future__ import annotations

import argparse
import json
import pathlib
import time

import jax
import numpy as np

from benchmarks.common import emit
from repro.configs import get_config, reduced
from repro.execution import available_executors
from repro.models import RunConfig, init_params
from repro.obs import latency_summary
from repro.quantization import available_schemes
from repro.scheduling import available_policies
from repro.serve.engine import Request, ServeEngine

PROMPT_LEN = 6


def run_cell(cfg, params, *, slots: int, policy: str, executor: str,
             steps: int, capacity: int, quant: str = "none",
             kv_block_size=None) -> dict:
    rc = RunConfig(q_chunk=64, kv_chunk=64, executor=executor,
                   schedule_policy=policy, quant=quant, moe_stats=False)
    eng = ServeEngine(cfg, params, slots=slots, capacity=capacity, rc=rc,
                      kv_block_size=kv_block_size)
    rng = np.random.default_rng(0)
    for i in range(slots):
        eng.admit(Request(rid=i,
                          prompt=rng.integers(0, cfg.vocab_size,
                                              PROMPT_LEN).astype(np.int32),
                          max_new=capacity))        # never retires in-window
    assert eng.n_active == slots
    for _ in range(2):                               # warmup: compile + cache
        eng.step()
    t0 = time.perf_counter()
    for _ in range(steps):
        n = eng.step()
        assert n == slots
    dt = time.perf_counter() - t0
    s_per_step = dt / steps
    tok_per_s = slots * steps / dt
    emit(f"serve_{policy}_slots{slots}", s_per_step,
         f"tok_per_s={tok_per_s:.1f}")
    return {"slots": slots, "policy": policy, "executor": executor,
            "quant": quant, "steps": steps, "s_per_step": s_per_step,
            "tok_per_s": tok_per_s, "kv_block": eng.kv_block_size,
            "kv_stats": eng.kv.stats() if eng.paged else None,
            "config": eng.describe(seed=0)}


# ----------------------------------------------------------------------
# Fused vs gathered paged-attention decode (DESIGN.md §12 acceptance)
# ----------------------------------------------------------------------
def run_paged_attn_compare(cfg, params, *, slots: int, steps: int,
                           capacity: int, kv_block: int) -> list:
    """Steady-state decode at ``slots`` active slots on the pallas
    executor, ``paged_attn`` fused vs gather.  Greedy tokens must be
    bitwise-identical (the fused kernel is a bit-for-bit companion of
    gather+flash); the throughput win is asserted on wall time on TPU
    and on the analytic HBM traffic everywhere (interpret-mode wall time
    orders the interpreter, not the memory system):

    * gather: reads the pool to materialize the (B, nb*bs, H, D) view,
      writes that view, and flash reads it back — 3x the KV bytes;
    * fused: the kernel DMAs each block-table-indexed tile exactly once.
    """
    from repro.kernels import ops
    cells = {}
    for mode in ("gather", "fused"):
        rc = RunConfig(q_chunk=64, kv_chunk=64, executor="pallas",
                       schedule_policy="dynamic", moe_stats=False,
                       paged_attn=mode)
        eng = ServeEngine(cfg, params, slots=slots, capacity=capacity,
                          rc=rc, kv_block_size=kv_block)
        assert eng.paged, "fused-vs-gather compare needs the paged cache"
        rng = np.random.default_rng(0)
        reqs = [Request(rid=i,
                        prompt=rng.integers(0, cfg.vocab_size,
                                            PROMPT_LEN).astype(np.int32),
                        max_new=capacity)         # never retires in-window
                for i in range(slots)]
        for r in reqs:
            eng.admit(r)
        assert eng.n_active == slots
        for _ in range(2):                        # warmup: compile + cache
            eng.step()
        t0 = time.perf_counter()
        for _ in range(steps):
            assert eng.step() == slots
        dt = time.perf_counter() - t0
        # per-step KV traffic of the attention read path: with all slots
        # active the gathered (B, nb*bs, ...) view IS the pool's extent
        pool_bytes = sum(leaf.nbytes
                         for leaf in jax.tree_util.tree_leaves(eng.kv.pools))
        kv_bytes = pool_bytes * (3 if mode == "gather" else 1)
        tok_per_s = slots * steps / dt
        emit(f"paged_attn_{mode}_slots{slots}", dt / steps,
             f"tok_per_s={tok_per_s:.1f}")
        cells[mode] = {"paged_attn": mode, "slots": slots, "steps": steps,
                       "s_per_step": dt / steps, "tok_per_s": tok_per_s,
                       "kv_bytes_per_step": kv_bytes,
                       "kv_block": eng.kv_block_size, "on_tpu": ops.on_tpu(),
                       "outputs": {r.rid: list(r.out) for r in reqs},
                       "config": eng.describe(seed=0)}
    fused, gather = cells["fused"], cells["gather"]
    # the fused kernel must not change a single sampled token
    assert fused["outputs"] == gather["outputs"], \
        "fused paged attention changed greedy decode tokens"
    assert fused["kv_bytes_per_step"] < gather["kv_bytes_per_step"]
    fused["kv_bytes_win"] = gather["kv_bytes_win"] = \
        gather["kv_bytes_per_step"] / fused["kv_bytes_per_step"]
    if ops.on_tpu():
        assert fused["tok_per_s"] > gather["tok_per_s"], \
            (f"fused paged decode slower than gather on TPU: "
             f"{fused['tok_per_s']:.1f} <= {gather['tok_per_s']:.1f} tok/s")
    print(f"# paged-attn decode @ {slots} slots: "
          f"{gather['tok_per_s']:.1f} tok/s (gather) vs "
          f"{fused['tok_per_s']:.1f} tok/s (fused); KV bytes/step "
          f"{gather['kv_bytes_per_step']:.2e} -> "
          f"{fused['kv_bytes_per_step']:.2e} "
          f"({fused['kv_bytes_win']:.1f}x analytic, tokens identical)")
    for c in cells.values():
        c.pop("outputs")
    return [gather, fused]


# ----------------------------------------------------------------------
# Mixed prefill/decode + shared-prefix workload (paged-cache acceptance)
# ----------------------------------------------------------------------
def run_workload_cell(cfg, params, *, mode: str, executor: str, slots: int,
                      capacity: int, n_req: int, prefix_len: int,
                      suffix_len: int, max_new: int, prefill_chunk: int,
                      kv_block: int) -> dict:
    """One RESIDENT request decodes throughout while ``n_req`` shared-
    prefix requests stream through the remaining slots.  Counts, besides
    wall time, the DETERMINISTIC costs: engine forwards (steps + the
    contiguous engine's admission prefills — each is one jit call, i.e.
    one DispatchPlan per MoE layer), prompt tokens that actually entered
    dispatch plans (prefix-cache hits never do), and the resident's decode
    tokens per forward — the "prefill stalls decoding" lever: a contiguous
    admission prefill is a forward in which the resident produces nothing,
    while a prefill chunk rides the resident's own decode plan.

    modes: ``paged`` (prefix cache + chunked prefill), ``paged_noprefix``
    (chunked prefill only), ``contiguous`` (pre-paging engine)."""
    rc = RunConfig(q_chunk=64, kv_chunk=64, executor=executor,
                   schedule_policy="dynamic", moe_stats=bool(cfg.is_moe))
    kw = {"paged": dict(kv_block_size=kv_block, prefix_cache=True,
                        prefill_chunk=prefill_chunk),
          "paged_noprefix": dict(kv_block_size=kv_block, prefix_cache=False,
                                 prefill_chunk=prefill_chunk),
          "contiguous": dict(kv_block_size=0)}[mode]
    eng = ServeEngine(cfg, params, slots=slots, capacity=capacity, rc=rc,
                      **kw)
    rng = np.random.default_rng(0)
    resident = Request(rid=10 ** 6,
                       prompt=rng.integers(0, cfg.vocab_size,
                                           4).astype(np.int32),
                       max_new=10 ** 9)           # never retires in-window
    prefix = rng.integers(0, cfg.vocab_size, prefix_len).astype(np.int32)
    reqs = [Request(rid=i,
                    prompt=np.concatenate(
                        [prefix, rng.integers(0, cfg.vocab_size, suffix_len)
                         ]).astype(np.int32),
                    max_new=max_new)
            for i in range(n_req)]
    eng.admit(resident)
    res_base = len(resident.out)
    pending = list(reqs)
    steps = admits = 0
    t0 = time.perf_counter()
    while not all(r.done for r in reqs):
        while pending and eng.n_active < eng.slots:
            eng.admit(pending.pop(0))
            admits += 1
        assert eng.step() > 0
        steps += 1
    dt = time.perf_counter() - t0
    decode_tokens = sum(len(r.out) for r in reqs)
    # contiguous admission runs a whole-prompt prefill forward per request;
    # paged admission runs none (chunks ride inside the counted steps)
    forwards = steps + (admits if not eng.paged else 0)
    resident_tokens = len(resident.out) - res_base
    hit = sum(r.stats.get("serve/prefix_hit_tokens", 0.0) for r in reqs)
    dispatched = sum(len(r.prompt) for r in reqs) - hit
    rec = {"mode": mode, "slots": slots, "n_req": n_req,
           "prefix_len": prefix_len, "suffix_len": suffix_len,
           "max_new": max_new, "prefill_chunk": prefill_chunk,
           "kv_block": (kv_block if mode != "contiguous" else 0),
           "decode_tokens": decode_tokens, "forwards": forwards,
           "prefill_dispatch_tokens": dispatched,
           "prefix_hit_tokens": hit,
           "resident_tokens": resident_tokens,
           "decode_tok_per_forward": resident_tokens / forwards,
           "wall_s": dt,
           "tok_per_s": (decode_tokens + resident_tokens) / dt,
           "latency": latency_summary(reqs),
           "kv_stats": eng.kv.stats() if eng.paged else None,
           "config": eng.describe(seed=0),
           "outputs": {r.rid: r.out for r in reqs}}
    emit(f"workload_{mode}", dt / max(forwards, 1),
         f"resident_tok_per_fwd={rec['decode_tok_per_forward']:.2f}")
    return rec


def run_shared_prefix_sweep(cfg, params, *, executor: str, smoke: bool):
    dims = dict(slots=2, capacity=128 if smoke else 256,
                n_req=4 if smoke else 8,
                prefix_len=24 if smoke else 48, suffix_len=4,
                max_new=6 if smoke else 16, prefill_chunk=8, kv_block=8)
    cells = {m: run_workload_cell(cfg, params, mode=m, executor=executor,
                                  **dims)
             for m in ("paged", "paged_noprefix", "contiguous")}
    paged, noprefix, contig = (cells["paged"], cells["paged_noprefix"],
                               cells["contiguous"])
    # tokens must be identical across cache layouts — else the speedups
    # below are measuring a correctness bug
    assert paged["outputs"] == noprefix["outputs"] == contig["outputs"]
    # prefix hits: later requests' shared blocks never enter a plan —
    # fewer prefill dispatch tokens AND fewer engine forwards
    assert paged["prefill_dispatch_tokens"] \
        < noprefix["prefill_dispatch_tokens"], cells
    assert paged["forwards"] < noprefix["forwards"], cells
    # the stream's first admission computes the prefix; every later one
    # must hit the full registered run
    full_prefix = (dims["prefix_len"] // dims["kv_block"]) * dims["kv_block"]
    assert paged["prefix_hit_tokens"] \
        >= (dims["n_req"] - 1) * full_prefix, cells
    # chunked prefill: the resident slot decodes in EVERY forward (chunks
    # ride its plan), while the contiguous engine stalls it one forward
    # per admission prefill — strictly higher decode tok/forward
    assert paged["decode_tok_per_forward"] \
        > contig["decode_tok_per_forward"], cells
    for c in cells.values():
        c.pop("outputs")
    print(f"# shared-prefix workload: prefill tokens dispatched "
          f"{contig['prefill_dispatch_tokens']:.0f} (contiguous) -> "
          f"{paged['prefill_dispatch_tokens']:.0f} (prefix cache); "
          f"decode tok/forward {contig['decode_tok_per_forward']:.2f} -> "
          f"{paged['decode_tok_per_forward']:.2f} (chunked prefill)")
    return list(cells.values())


# ----------------------------------------------------------------------
# Expert-parallel scaling (padding-free a2a vs static layout + curves)
# ----------------------------------------------------------------------
def run_ep_scaling(*, smoke: bool, out_dir: pathlib.Path) -> None:
    """EP dispatch scaling on a >1-device mesh + the a2a payload
    accounting that motivates the padding-free send path.

    Payload table (analytic, per source rank): the padding-free transport
    commits ``ep * a2a_send_rows`` rows; the legacy static layout ships
    ``E * expert_capacity`` rows no matter what routed where.  The
    serving regime (many experts, modest per-rank token count — the
    DeepSeek-style E=64 cell here) is where padding-free wins; the
    acceptance bar (dynamic under zipf2.0 skew strictly below static) is
    asserted, with the actually-USED rows under a zipf2.0 draw recorded
    alongside.  Timed curves run the real sharded dispatch (and the
    overlapped variant) at each mesh size the host exposes — launch under
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` on CPU."""
    from benchmarks.common import time_fn, zipf_assignments
    from repro.configs.base import MoEConfig
    from repro.core import dispatch_config, init_moe_params
    from repro.core.distributed import (a2a_send_rows, a2a_send_rows_static,
                                        apply_moe_ep)
    from repro.launch.mesh import make_ep_mesh

    E, k, M, d, Tl, cf = 64, 2, 16, 32, 64, 2.0
    eps = [1, 2, 4]
    ndev = jax.local_device_count()

    payload = []
    static_rows = a2a_send_rows_static(Tl, k, E, M, cf)
    _, idx = zipf_assignments(jax.random.key(7), Tl, k, E, 2.0)
    for policy in ("fixed", "dynamic", "capacity_factor"):
        for ep in eps:
            C = a2a_send_rows(Tl, k, E, ep, M, cf, policy)
            dest = np.asarray(idx).reshape(-1) // (E // ep)
            used = int(np.bincount(dest, minlength=ep).max())
            payload.append({
                "policy": policy, "ep": ep, "skew": "zipf2.0",
                "rows_padding_free": ep * C, "rows_static": static_rows,
                "rows_used_max_dest": used,
                "payload_ratio": ep * C / static_rows})
    for rec in payload:
        if rec["policy"] in ("dynamic", "capacity_factor"):
            assert rec["rows_padding_free"] < rec["rows_static"], (
                "padding-free a2a payload must undercut the static "
                "layout in the many-expert serving regime", rec)
    print(f"# payload (per-rank a2a rows, E={E} k={k} M={M} Tl={Tl}): "
          f"static={static_rows}; padding-free "
          + ", ".join(f"{r['policy']}@ep{r['ep']}={r['rows_padding_free']}"
                      for r in payload if r["ep"] == max(eps)))

    moe = MoEConfig(n_experts=E, top_k=k, d_ff_expert=32, block_m=M,
                    capacity_factor=cf)
    params = init_moe_params(jax.random.key(0), moe, d)
    curves = []
    steps = 2 if smoke else 8
    for ep in [e for e in eps if e <= ndev]:
        mesh = make_ep_mesh(ep)
        T = Tl * ep                       # weak scaling: Tl fixed per rank
        x = jax.random.normal(jax.random.key(1), (1, T, d))
        for policy in ("dynamic", "capacity_factor"):
            dcfg = dispatch_config(moe, executor="xla",
                                   schedule_policy=policy)
            for overlap in ((0, 2) if ep > 1 else (0,)):
                with jax.set_mesh(mesh):
                    fn = jax.jit(lambda p, x, o=overlap, c=dcfg:
                                 apply_moe_ep(p, x, c, overlap=o)[0])
                    t = time_fn(fn, params, x, warmup=1, iters=steps)
                tok_per_s = T / t
                curves.append({
                    "ep": ep, "policy": policy, "overlap": overlap,
                    "tokens": T, "s_per_call": t, "tok_per_s": tok_per_s})
                emit(f"ep_scaling/{policy}/ep{ep}"
                     f"{'/overlap' if overlap else ''}", t,
                     f"tok_per_s={tok_per_s:.1f}")
    if ndev < max(eps):
        print(f"# note: only {ndev} device(s) visible — curves above "
              f"ep={ndev} skipped (force more with XLA_FLAGS)")

    out_dir.mkdir(parents=True, exist_ok=True)
    out_path = out_dir / f"ep_scaling{'_smoke' if smoke else ''}.json"
    out_path.write_text(json.dumps(
        {"regime": {"n_experts": E, "top_k": k, "block_m": M,
                    "tokens_per_rank": Tl, "capacity_factor": cf,
                    "d_model": d},
         "payload_rows": payload, "curves": curves,
         "devices": ndev}, indent=1))
    print(f"# wrote {out_path}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="moonshot-v1-16b-a3b")
    ap.add_argument("--slots", default="1,2,4,8",
                    help="comma-separated active-slot counts to sweep")
    ap.add_argument("--policies", default="fixed,dynamic",
                    help=f"comma-separated schedule policies "
                         f"(registered: {','.join(available_policies())})")
    ap.add_argument("--executor", default="xla",
                    choices=available_executors())
    ap.add_argument("--quant", default="none",
                    choices=available_schemes(),
                    help="expert-weight quantization scheme "
                         "(repro.quantization registry)")
    ap.add_argument("--steps", type=int, default=32)
    ap.add_argument("--capacity", type=int, default=128)
    ap.add_argument("--kv-block-size", type=int, default=None,
                    help="paged cache block size for the decode sweep "
                         "(0 = contiguous; default auto)")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny sweep for CI: slots 1,2 / 4 steps")
    ap.add_argument("--out", default="results/serve",
                    help="output dir for the JSON records")
    ap.add_argument("--ep-scaling", action="store_true",
                    help="run ONLY the expert-parallel scaling sweep "
                         "(padding-free vs static a2a payload + dispatch "
                         "curves on a >1-device mesh); CPU needs "
                         "XLA_FLAGS=--xla_force_host_platform_device_"
                         "count=N")
    args = ap.parse_args()

    if args.ep_scaling:
        run_ep_scaling(smoke=args.smoke, out_dir=pathlib.Path(args.out))
        return

    slot_counts = [int(s) for s in args.slots.split(",")]
    steps = args.steps
    if args.smoke:
        slot_counts = [1, 2]
        steps = 4
    # steady-state requires no retirement inside warmup(2)+steps decodes:
    # a slot retires when its position hits capacity - 1
    max_steps = args.capacity - 1 - PROMPT_LEN - 2
    if steps > max_steps:
        raise SystemExit(
            f"--steps {steps} exceeds the capacity headroom: at most "
            f"{max_steps} timed steps fit before a slot retires "
            f"(capacity {args.capacity} - prompt {PROMPT_LEN} - warmup 2); "
            f"raise --capacity or lower --steps")

    cfg = reduced(get_config(args.arch))
    params = init_params(cfg, jax.random.key(0))
    print(f"# {args.arch} (reduced) — decode throughput, "
          f"slots={slot_counts} x policies={args.policies} "
          f"[executor={args.executor}, quant={args.quant}]")
    print("name,us_per_call,derived")

    records = []
    for policy in args.policies.split(","):
        for slots in slot_counts:
            records.append(run_cell(cfg, params, slots=slots, policy=policy,
                                    executor=args.executor, steps=steps,
                                    capacity=args.capacity,
                                    quant=args.quant,
                                    kv_block_size=args.kv_block_size))

    from repro.serve.kv_cache import paged_supported
    if paged_supported(cfg):
        shared_prefix = run_shared_prefix_sweep(cfg, params,
                                                executor=args.executor,
                                                smoke=args.smoke)
        # the ≥8-slot fused-vs-gather decode cell (pallas executor; the
        # modes differ only in the attention read path)
        paged_attn = run_paged_attn_compare(
            cfg, params, slots=8, steps=4 if args.smoke else 16,
            capacity=args.capacity, kv_block=8)
    else:
        shared_prefix = []
        paged_attn = []
        print(f"# shared-prefix workload skipped: {args.arch} has "
              f"non-pageable caches (contiguous engine only)")

    out_dir = pathlib.Path(args.out)
    out_dir.mkdir(parents=True, exist_ok=True)
    suffix = "_smoke" if args.smoke else ""
    out_path = out_dir / f"{args.arch}{suffix}.json"
    out_path.write_text(json.dumps({"arch": args.arch, "reduced": True,
                                    "records": records,
                                    "shared_prefix": shared_prefix,
                                    "paged_attn": paged_attn},
                                   indent=1))
    print(f"# wrote {out_path}")

    for policy in args.policies.split(","):
        by_slots = {r["slots"]: r for r in records if r["policy"] == policy}
        lo, hi = min(by_slots), max(by_slots)
        gain = by_slots[hi]["tok_per_s"] / by_slots[lo]["tok_per_s"]
        print(f"# {policy}: {by_slots[lo]['tok_per_s']:.1f} tok/s @ {lo} "
              f"slot(s) -> {by_slots[hi]['tok_per_s']:.1f} tok/s @ {hi} "
              f"slots ({gain:.2f}x)")
        if not args.smoke:
            assert gain > 1.0, \
                (f"{policy}: batched decode throughput did not increase "
                 f"with slot count ({gain:.2f}x)")


if __name__ == "__main__":
    main()
