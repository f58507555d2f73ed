"""Serving launcher: load (or init) weights, optionally quantize the
routed experts under a registered scheme (`--quant`, DESIGN.md §8 — int8
per-expert is the §Perf cell-3 deployment layout), and run batched
requests through the continuous-batching engine — all active slots decode
in ONE jitted step over a single batched KV cache, so every MoE layer
dispatches the whole decode batch in one plan.

    PYTHONPATH=src python -m repro.launch.serve --arch moonshot-v1-16b-a3b \\
        --reduce --requests 6 --quant int8_expert --executor xla --slots 4
"""
import argparse


def build_parser() -> argparse.ArgumentParser:
    from repro.execution import available_executors
    from repro.quantization import available_schemes
    from repro.sampling import available_samplers
    from repro.scheduling import available_policies
    from repro.serve.admission import available_admission_policies

    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduce", action="store_true")
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--slots", type=int, default=2,
                    help="decode slots = rows of the batched KV cache; all "
                         "active slots decode together in one jitted step")
    ap.add_argument("--capacity", type=int, default=128,
                    help="per-slot KV cache capacity (tokens)")
    ap.add_argument("--kv-block-size", type=int, default=None,
                    help="paged KV cache block size in tokens (DESIGN.md "
                         "§9); 0 forces the contiguous pre-paging cache; "
                         "default: auto (paged wherever the architecture's "
                         "caches are positional KV)")
    ap.add_argument("--prefix-cache", dest="prefix_cache",
                    action="store_true", default=True,
                    help="content-hash full prompt blocks and share them "
                         "across requests (paged mode; default on)")
    ap.add_argument("--no-prefix-cache", dest="prefix_cache",
                    action="store_false")
    ap.add_argument("--prefill-chunk", type=int, default=32,
                    help="chunked prefill: prompt tokens fed per step into "
                         "the decode step's shared dispatch plan (paged "
                         "mode; prefill never stalls decoding slots)")
    ap.add_argument("--max-steps", type=int, default=512,
                    help="decode-step budget for the whole run; requests "
                         "still in flight when it runs out are reported "
                         "(done=False, partial output kept)")
    ap.add_argument("--quant", default=None, choices=available_schemes(),
                    help="expert-weight quantization scheme "
                         "(repro.quantization registry; default: none)")
    ap.add_argument("--quant-experts", action="store_true",
                    help="DEPRECATED: alias for --quant int8_expert")
    ap.add_argument("--executor", default="xla",
                    choices=available_executors(),
                    help="MoE executor backend (repro.execution registry)")
    ap.add_argument("--autotune", action="store_true",
                    help="consult the persistent kernel tune cache "
                         "(results/tuning/cache.json, DESIGN.md §12) for "
                         "swept block sizes instead of the hard-coded "
                         "defaults (pallas executor)")
    ap.add_argument("--paged-attn", default="auto",
                    choices=("auto", "fused", "gather"),
                    help="paged decode attention path: 'fused' = one "
                         "Pallas kernel walks the block table (no "
                         "gathered-cache materialization), 'gather' = "
                         "pool gather + flash, 'auto' = fused iff the "
                         "executor is pallas")
    ap.add_argument("--schedule-policy", default="dynamic",
                    choices=available_policies(),
                    help="MoE schedule policy (serving default: dynamic)")
    ap.add_argument("--sampling", default="greedy",
                    choices=available_samplers(),
                    help="token selection (repro.sampling registry); "
                         "greedy keeps the bitwise-exact argmax path, the "
                         "stochastic methods draw keyed per-request "
                         "streams on device (one host sync per step)")
    ap.add_argument("--temperature", type=float, default=1.0)
    ap.add_argument("--top-k", type=int, default=0,
                    help="top-k truncation for --sampling top_k (0 = off)")
    ap.add_argument("--top-p", type=float, default=1.0,
                    help="nucleus mass for --sampling top_p (1.0 = off)")
    ap.add_argument("--seed", type=int, default=0,
                    help="engine-level sampling seed base; request i draws "
                         "from stream seed+i (stochastic methods only)")
    ap.add_argument("--spec-draft", default=None, metavar="ARCH",
                    help="enable speculative decoding with this draft "
                         "architecture (e.g. smollm-360m; reduced "
                         "alongside --reduce, vocab aligned to the "
                         "target); requires the paged engine")
    ap.add_argument("--spec-k", type=int, default=4,
                    help="draft tokens proposed per slot per speculative "
                         "round (target verifies k+1 positions in one "
                         "forward)")
    ap.add_argument("--admission", default="fcfs",
                    choices=available_admission_policies(),
                    help="which pending request gets a freed slot "
                         "(fcfs, sjf = shortest prompt, prefix_hit = "
                         "warmest cached prefix, slo = TTFT-deadline "
                         "feasibility with preemption)")
    ap.add_argument("--stream", action="store_true",
                    help="serve through the open-stream front-end "
                         "(repro.serve.frontend) and print each token as "
                         "the step's host sync retires it — streamed "
                         "sequences are bitwise-identical to the closed-"
                         "batch run")
    ap.add_argument("--slo-ttft", type=float, default=None, metavar="S",
                    help="per-request time-to-first-token deadline "
                         "(seconds); pair with --admission slo")
    ap.add_argument("--slo-tpot", type=float, default=None, metavar="S",
                    help="per-request time-per-output-token budget "
                         "(seconds); pair with --admission slo")
    ap.add_argument("--loadgen", default=None, metavar="PATTERN",
                    help="replay a seeded arrival trace (poisson | burst "
                         "| shared_prefix | longtail) on VIRTUAL time "
                         "through the front-end instead of a closed "
                         "batch; writes the goodput artifact to "
                         "results/serve/loadgen_<arch>[_smoke].json")
    ap.add_argument("--smoke", action="store_true",
                    help="with --loadgen: tiny trace for CI")
    ap.add_argument("--calibrate", action="store_true",
                    help="with --loadgen: advance the virtual clock by "
                         "the measured per-step wall-time EWMA instead "
                         "of a fixed 0.05 s (host-dependent goodput)")
    ap.add_argument("--trace", nargs="?", const="results/trace/serve.json",
                    default=None, metavar="PATH",
                    help="write a Chrome-trace/Perfetto JSON of the step "
                         "timeline (admit / prefix probe / assemble / "
                         "forward / host sync / retire + recompile and "
                         "slow-step instants); default path "
                         "results/trace/serve.json")
    ap.add_argument("--metrics-out", nargs="?",
                    const="results/serve/metrics.json", default=None,
                    metavar="PATH",
                    help="write the metrics-registry snapshot (counters/"
                         "gauges/histograms + TTFT/TPOT latency "
                         "percentiles) as JSON")
    ap.add_argument("--device-trace", default=None, metavar="DIR",
                    help="bracket the run in a jax.profiler device trace "
                         "written to DIR (the run fails if the profiler "
                         "cannot start or stop)")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--distributed", action="store_true",
                    help="expert-parallel serving over every visible "
                         "device: per-host admission queues feed ONE "
                         "global decode step (serve/distributed.py). "
                         "With --num-processes > 1 the processes join a "
                         "jax.distributed group first (multi-host mesh)")
    ap.add_argument("--coordinator", default="localhost:12355",
                    help="jax.distributed coordinator address "
                         "(process 0 binds it)")
    ap.add_argument("--num-processes", type=int, default=1)
    ap.add_argument("--process-id", type=int, default=0)
    ap.add_argument("--hosts", type=int, default=None,
                    help="admission host-queue count (default: "
                         "num-processes)")
    ap.add_argument("--ep-devices", type=int, default=None,
                    help="devices on the EP mesh axis (default: all); "
                         "single-process CPU runs force at least this "
                         "many host devices")
    ap.add_argument("--ep-overlap", action="store_true",
                    help="software-pipeline the sharded EP dispatch "
                         "(a2a of microbatch i+1 overlaps GEMMs of i)")
    ap.add_argument("--ep-microbatches", type=int, default=2)
    ap.add_argument("--ep-decode-layout", default="replicated",
                    choices=("replicated", "sharded"),
                    help="EP token layout for decode steps")
    return ap


def ep_mesh(args):
    """The ``--distributed`` EP mesh over axis 'model' (joining the
    ``jax.distributed`` group first when ``--num-processes`` > 1)."""
    import jax

    from repro.launch.mesh import (init_distributed, make_ep_mesh,
                                   multiprocess_compute_supported)
    if args.num_processes > 1:
        init_distributed(args.coordinator, args.num_processes,
                         args.process_id)
        if not multiprocess_compute_supported():
            raise SystemExit(
                "the active backend cannot run multi-process "
                "computations (CPU): re-launch single-process with "
                "--ep-devices N for a forced-host-device mesh")
    mesh = make_ep_mesh(args.ep_devices, axis="model")
    print(f"distributed serving: {jax.process_count()} process(es), "
          f"EP mesh {mesh.devices.shape} over axis 'model', "
          f"decode layout {args.ep_decode_layout}, overlap "
          f"{'on' if args.ep_overlap else 'off'}")
    return mesh


def build_engine(args, cfg, *, obs):
    """The launcher's serving engine for parsed ``args`` over ``cfg``.

    Params are random from seed 0 (or restored from ``--ckpt-dir``);
    routed experts are quantized under ``--quant``; the result is a
    ``ServeEngine`` or, with ``--spec-draft``, a ``SpecEngine``.  Under
    ``--distributed`` the EP mesh must already be set (``jax.set_mesh``):
    params are then created in place on it, each device holding only
    its share of every routed-expert stack."""
    import jax

    from repro.configs import get_config, reduced
    from repro.models import RunConfig, init_params
    from repro.quantization import resolve_quant_cli
    from repro.sampling import SamplingConfig
    from repro.serve.engine import ServeEngine
    from repro.spec import SpecEngine

    def init():
        return init_params(cfg, jax.random.key(0))

    shardings = None
    if args.distributed:
        from jax.sharding import NamedSharding
        from repro.distributed.sharding import ep_param_specs
        mesh = jax.sharding.get_mesh()
        shardings = jax.tree.map(
            lambda s: NamedSharding(mesh, s),
            ep_param_specs(jax.eval_shape(init), cfg, axis="model"))
    if args.ckpt_dir:
        from repro.checkpoint.manager import CheckpointManager
        mgr = CheckpointManager(args.ckpt_dir)
        state = mgr.restore(jax.eval_shape(lambda: {"params": init()}),
                            shardings=(None if shardings is None
                                       else {"params": shardings}))
        params = state["params"]
    else:
        params = jax.jit(init, out_shardings=shardings)()
    quant = resolve_quant_cli(args.quant, args.quant_experts)
    if quant != "none" and cfg.is_moe:
        print(f"routed experts quantized under scheme {quant!r} "
              f"(serving layout)")

    sampling = SamplingConfig(method=args.sampling,
                              temperature=args.temperature,
                              top_k=args.top_k, top_p=args.top_p,
                              seed=args.seed)
    kw = dict(slots=args.slots,
              capacity=args.capacity, admission=args.admission,
              kv_block_size=args.kv_block_size,
              prefix_cache=args.prefix_cache,
              prefill_chunk=args.prefill_chunk, obs=obs,
              sampling=sampling,
              rc=RunConfig(q_chunk=64, kv_chunk=64,
                           executor=args.executor,
                           schedule_policy=args.schedule_policy,
                           quant=quant if cfg.is_moe else "none",
                           moe_stats=bool(cfg.is_moe),
                           autotune=args.autotune,
                           paged_attn=args.paged_attn,
                           ep=bool(args.distributed and cfg.is_moe),
                           ep_overlap=args.ep_overlap,
                           ep_microbatches=args.ep_microbatches,
                           ep_decode_layout=args.ep_decode_layout))
    if args.spec_draft:
        draft_cfg = get_config(args.spec_draft)
        if args.reduce:
            draft_cfg = reduced(draft_cfg)
        draft_cfg = draft_cfg.replace(vocab_size=cfg.vocab_size)
        draft_params = init_params(draft_cfg, jax.random.key(1))
        engine = SpecEngine(cfg, params, draft_cfg=draft_cfg,
                            draft_params=draft_params, spec_k=args.spec_k,
                            **kw)
        print(f"speculative decoding: draft {draft_cfg.name} proposes "
              f"k={args.spec_k} tokens/slot/round; target verifies "
              f"{args.spec_k + 1} positions per slot in one forward")
    else:
        engine = ServeEngine(cfg, params, **kw)
    return engine


def main():
    args = build_parser().parse_args()

    import contextlib
    import os

    if args.distributed and args.num_processes == 1:
        # single-process fallback (CPU smoke): the EP collectives still
        # need >1 device, so force a multi-device host platform BEFORE
        # jax initializes
        n_dev = args.ep_devices or 2
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                f"{flags} --xla_force_host_platform_device_count="
                f"{n_dev}").strip()

    import numpy as np
    import jax

    from repro.configs import get_config, reduced
    from repro.launch import use_compile_cache
    from repro.obs import (NOOP, Observability, device_trace, drop_summary,
                           latency_summary)
    from repro.serve.engine import Request
    from repro.serve.frontend import ServingFrontend
    from repro.serve.loadgen import make_virtual_obs, replay, synth_trace
    from repro.spec import SpecEngine

    use_compile_cache()

    cfg = get_config(args.arch)
    if args.reduce:
        cfg = reduced(cfg)
    if not cfg.has_decode:
        raise SystemExit(f"{args.arch} is encoder-only: no decode path")

    mesh_stack = contextlib.ExitStack()
    if args.distributed:
        mesh_stack.enter_context(jax.set_mesh(ep_mesh(args)))

    if args.loadgen:
        clock, obs = make_virtual_obs(enabled=True)
    else:
        clock = None
        obs = (Observability.memory()
               if (args.trace or args.metrics_out or args.device_trace)
               else NOOP)
    engine = build_engine(args, cfg, obs=obs)
    if engine.paged:
        print(f"paged KV cache: {engine.kv.n_blocks} blocks x "
              f"{engine.kv.block_size} tokens, prefix cache "
              f"{'on' if engine.kv.prefix_cache else 'off'}, "
              f"prefill chunk {engine.prefill_chunk}")
    else:
        print("contiguous KV cache (non-pageable family or "
              "--kv-block-size 0)")
    if args.loadgen:
        import json
        import pathlib

        n = 12 if args.smoke else 24
        trace = synth_trace(args.loadgen, seed=0, n=n, rate=8.0,
                            vocab=cfg.vocab_size, max_new=args.max_new,
                            slo_ttft=args.slo_ttft if args.slo_ttft
                            is not None else 0.4,
                            slo_tpot=args.slo_tpot,
                            burst_size=6, prompt_hi=40)
        rec = replay(engine, trace, clock=clock,
                     step_time=None if args.calibrate else 0.05, seed=0,
                     pattern=args.loadgen,
                     max_steps=min(args.max_steps, 1024))
        rec.pop("outputs", None)
        out_path = pathlib.Path("results/serve")
        out_path.mkdir(parents=True, exist_ok=True)
        out_path = out_path / (f"loadgen_{args.arch}"
                               f"{'_smoke' if args.smoke else ''}.json")
        out_path.write_text(json.dumps(
            {"arch": args.arch, "reduced": args.reduce,
             "virtual_time": True,
             "step_time_mode": rec["step_time_mode"],
             "records": [rec]}, indent=1))
        print(f"loadgen {args.loadgen}: {rec['completed']}/"
              f"{rec['n_requests']} completed, goodput "
              f"{rec['goodput_rps']:.3f} req/s, attainment "
              f"{rec['slo_attainment']:.2f}, preempted {rec['preempted']}, "
              f"resumed {rec['resumed']}, TTFT p50 "
              f"{rec['ttft_p50_s']} s")
        print(f"loadgen artifact -> {out_path}")
        return

    rng = np.random.default_rng(0)
    reqs = [Request(rid=i,
                    prompt=rng.integers(0, cfg.vocab_size,
                                        rng.integers(3, 9)).astype(np.int32),
                    max_new=args.max_new,
                    slo_ttft=args.slo_ttft, slo_tpot=args.slo_tpot)
            for i in range(args.requests)]
    bracket = (device_trace(args.device_trace) if args.device_trace
               else contextlib.nullcontext())
    with bracket:
        if args.stream:
            fe = ServingFrontend(engine)
            handles = [fe.submit(r.prompt, max_new=r.max_new, rid=r.rid,
                                 slo_ttft=r.slo_ttft, slo_tpot=r.slo_tpot,
                                 on_token=lambda req, tok:
                                 print(f"  stream rid={req.rid} "
                                       f"tok[{len(req.out) - 1}]={tok}"))
                       for r in reqs]
            done = fe.drain(max_steps=args.max_steps)
            reqs = handles
            engine.dropped = [r for r in reqs if not r.done]
        elif args.distributed:
            from repro.serve.distributed import DistributedServeLoop
            loop = DistributedServeLoop(
                engine, n_hosts=args.hosts or max(1, args.num_processes),
                admission=args.admission)
            done = loop.run(reqs, max_steps=args.max_steps)
        else:
            done = engine.run(reqs, max_steps=args.max_steps)
    for r in reqs:
        tag = "" if r.done else "  [INCOMPLETE: step budget exhausted]"
        print(f"req {r.rid}: {r.prompt.tolist()} -> {r.out}{tag}")
        if r.stats:
            sched = {k.split("/", 1)[1]: round(v, 3)
                     for k, v in r.stats.items() if k.startswith("sched/")}
            if sched:
                print(f"  plan stats (last step, shared by "
                      f"{int(r.stats.get('serve/decode_batch', 1))} slot(s), "
                      f"summed over moe layers): {sched}")
    print(f"{len(done)}/{len(reqs)} requests completed")
    if isinstance(engine, SpecEngine):
        print(f"speculation: {engine.n_spec_rounds} rounds, "
              f"{engine.n_accepted}/{engine.n_drafted} drafts accepted "
              f"(rate {engine.acceptance_rate:.2f}); "
              f"{engine.n_forwards} target + {engine.n_draft_forwards} "
              f"draft forwards")
    # completion percentiles over COMPLETED requests only — censored
    # (dropped/preempted) stats are rolled up separately below
    lat = latency_summary([r for r in reqs if r.done])
    if any(lat.values()):
        for fam in ("ttft_s", "tpot_s", "queue_wait_s", "e2e_s"):
            agg = lat.get(fam)
            if agg:
                print(f"  {fam:>13}: mean {agg['mean'] * 1e3:8.2f} ms  "
                      f"p50 {agg['p50'] * 1e3:8.2f} ms  "
                      f"p99 {agg['p99'] * 1e3:8.2f} ms  (n={agg['n']})")
    if engine.paged:
        print(f"paged-cache stats: {engine.kv.stats()}")
    drops = drop_summary(reqs)
    if drops:
        wait = drops["wait_s"]
        tail = (f"; censored wait p50 {wait['p50'] * 1e3:.1f} ms"
                if wait else "")
        print(f"WARNING: {drops['n']} request(s) did not complete under "
              f"--max-steps={args.max_steps} "
              f"({drops['dropped']} dropped, {drops['preempted']} "
              f"preempted-unresumed; rids {drops['rids']}); "
              f"{drops['tokens_out']} partial token(s) retained on "
              f"Request.out{tail}")
    if args.trace:
        path = engine.obs.tracer.save(args.trace)
        print(f"chrome trace ({len(engine.obs.tracer.events)} events) "
              f"-> {path}")
    if args.metrics_out:
        extra = {"latency": lat}
        if engine.paged:
            extra["kv_stats"] = engine.kv.stats()
        engine.obs.metrics.to_json(args.metrics_out, extra=extra)
        print(f"metrics snapshot -> {args.metrics_out}")


if __name__ == "__main__":
    main()
