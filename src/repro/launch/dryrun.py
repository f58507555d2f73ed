import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"
# The lines above MUST run before any other import (jax locks the device
# count at first init). 512 placeholder host devices back the production
# meshes: 16x16 single-pod, 2x16x16 multi-pod.  The dry run is a CPU-only
# tool: it and the per-cell subprocesses it starts (which inherit this
# environment) never open an accelerator, so it can run beside a process
# that holds the chip.

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

Per cell: build abstract inputs (launch/specs.py), install sharding rules,
``jax.jit(step, in_shardings, out_shardings).lower(...).compile()`` on the
production mesh, then record ``memory_analysis()`` / ``cost_analysis()`` and
the parsed collective-byte totals (analysis/hlo.py) to a JSON file that
EXPERIMENTS.md §Dry-run / §Roofline read from.

Usage:
  python -m repro.launch.dryrun --arch smollm-360m --shape train_4k
  python -m repro.launch.dryrun --arch ... --shape ... --multi-pod
  python -m repro.launch.dryrun --all [--multi-pod] [--jobs-file cells.txt]
"""
import argparse
import json
import pathlib
import subprocess
import sys
import time
import traceback

RESULT_DIR = pathlib.Path(__file__).resolve().parents[3] / "results" / "dryrun"


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             save_hlo: bool = False, accum=None, layout: str = "fsdp",
             pin_grads: bool = False, capacity_factor=None,
             variant: str = "", drop_rules=(),
             quant: str = "none", executor: str = None) -> dict:
    import jax

    from repro.analysis.hlo import collective_report
    from repro.configs import SHAPE_BY_NAME, cell_is_runnable, get_config
    from repro.distributed.ctx import use_rules
    from repro.launch.mesh import make_production_mesh
    from repro.launch.specs import cell_inputs

    cfg = get_config(arch)
    shape = SHAPE_BY_NAME[shape_name]
    ok, why = cell_is_runnable(cfg, shape)
    rec: dict = {"arch": arch, "shape": shape_name,
                 "mesh": "2x16x16" if multi_pod else "16x16"}
    if not ok:
        rec.update(status="skip", reason=why)
        return rec

    mesh = make_production_mesh(multi_pod=multi_pod)
    from repro.launch.specs import dryrun_runconfig
    rc = dryrun_runconfig(cfg, shape)
    if capacity_factor is not None:
        rc = rc._replace(capacity_factor=capacity_factor)
    if executor is not None:
        rc = rc._replace(executor=executor)
    rc = rc._replace(quant=quant)
    ci = cell_inputs(arch, shape, mesh, rc, accum=accum, layout=layout,
                     pin_grads=pin_grads)
    for r in drop_rules:
        ci.rules.pop(r, None)
    if variant:
        rec["variant"] = variant
    # donate the mutable aggregate (train state / decode cache) so XLA
    # aliases it in-place instead of holding input+output copies live
    donate = ()
    if ci.meta.get("mode") == "train":
        donate = (0,)
    elif ci.meta.get("mode") == "decode":
        donate = (2,)
    t0 = time.time()
    try:
        with jax.set_mesh(mesh), use_rules(mesh, ci.rules):
            jitted = jax.jit(ci.step_fn, in_shardings=ci.in_shardings,
                             out_shardings=ci.out_shardings,
                             donate_argnums=donate)
            lowered = jitted.lower(*ci.args)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower
        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        hlo = compiled.as_text()
        from repro.models.lm import group_structure
        _, _, n_groups, _ = group_structure(cfg)
        coll = collective_report(hlo, layer_trips=n_groups,
                                 accum_trips=ci.meta.get("accum", 1))
        rec.update(
            status="ok",
            lower_s=round(t_lower, 1), compile_s=round(t_compile, 1),
            meta=ci.meta,
            memory={
                "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
                "output_bytes": getattr(mem, "output_size_in_bytes", None),
                "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
                "alias_bytes": getattr(mem, "alias_size_in_bytes", None),
                "code_bytes": getattr(mem, "generated_code_size_in_bytes",
                                      None),
            },
            cost={k: cost.get(k) for k in
                  ("flops", "bytes accessed", "transcendentals")
                  if k in cost},
            collectives=coll,
        )
        if save_hlo:
            p = RESULT_DIR / f"{arch}.{shape_name}.{rec['mesh']}.hlo"
            p.parent.mkdir(parents=True, exist_ok=True)
            p.write_text(hlo)
            rec["hlo_path"] = str(p)
    except Exception as e:  # noqa: BLE001 — a failing cell is a bug report
        rec.update(status="error", error=f"{type(e).__name__}: {e}",
                   traceback=traceback.format_exc()[-4000:])
    return rec


def all_cells():
    from repro.configs import ARCH_NAMES, SHAPES
    return [(a, s.name) for a in ARCH_NAMES for s in SHAPES]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--save-hlo", action="store_true")
    ap.add_argument("--accum", type=int, default=None)
    ap.add_argument("--layout", default="fsdp", choices=["fsdp", "serve_tp"])
    ap.add_argument("--pin-grads", action="store_true")
    ap.add_argument("--capacity-factor", type=float, default=None)
    ap.add_argument("--variant", default="",
                    help="tag appended to the output filename (perf runs)")
    ap.add_argument("--drop-rule", action="append", default=[],
                    help="remove an activation-sharding rule (perf exp)")
    ap.add_argument("--quant", default=None,
                    help="expert-weight quantization scheme "
                         "(repro.quantization registry; default: none)")
    ap.add_argument("--quant-experts", action="store_true",
                    help="DEPRECATED: alias for --quant int8_expert")
    ap.add_argument("--executor", default=None,
                    help="MoE executor backend override "
                         "(repro.execution registry; default: xla)")
    ap.add_argument("--out", default=str(RESULT_DIR))
    args = ap.parse_args()
    out = pathlib.Path(args.out)
    out.mkdir(parents=True, exist_ok=True)

    if args.all:
        # sweep in subprocesses (fresh XLA state per cell; fault isolation)
        meshes = [False, True] if args.both_meshes else [args.multi_pod]
        failures = 0
        for arch, shape in all_cells():
            for mp in meshes:
                tag = f"{arch}.{shape}.{'2x16x16' if mp else '16x16'}"
                dest = out / f"{tag}.json"
                if dest.exists() and \
                        json.loads(dest.read_text()).get("status") == "ok":
                    print(f"[skip-done] {tag}", flush=True)
                    continue
                cmd = [sys.executable, "-m", "repro.launch.dryrun",
                       "--arch", arch, "--shape", shape, "--out", str(out)]
                if mp:
                    cmd.append("--multi-pod")
                if args.save_hlo:
                    cmd.append("--save-hlo")
                t0 = time.time()
                r = subprocess.run(cmd, capture_output=True, text=True)
                dt = time.time() - t0
                status = "?"
                if dest.exists():
                    status = json.loads(dest.read_text()).get("status")
                print(f"[{status:5s}] {tag}  {dt:6.1f}s", flush=True)
                if status not in ("ok", "skip"):
                    failures += 1
                    if r.stderr:
                        print(r.stderr[-2000:], flush=True)
        return 1 if failures else 0

    from repro.launch import use_compile_cache
    from repro.quantization import resolve_quant_cli
    use_compile_cache()
    rec = run_cell(args.arch, args.shape, args.multi_pod,
                   save_hlo=args.save_hlo, accum=args.accum,
                   layout=args.layout, pin_grads=args.pin_grads,
                   capacity_factor=args.capacity_factor,
                   variant=args.variant, drop_rules=args.drop_rule,
                   quant=resolve_quant_cli(args.quant, args.quant_experts),
                   executor=args.executor)
    tag = f"{args.arch}.{args.shape}.{rec['mesh']}"
    if args.variant:
        tag += f".{args.variant}"
    dest = out / f"{tag}.json"
    dest.write_text(json.dumps(rec, indent=2))
    print(json.dumps({k: v for k, v in rec.items()
                      if k not in ("traceback",)}, indent=2))
    if rec["status"] == "error":
        print(rec.get("traceback", ""), file=sys.stderr)
    return 0 if rec["status"] in ("ok", "skip") else 1


if __name__ == "__main__":
    sys.exit(main())
