"""Production training launcher.

Wires mesh construction, parameter/batch/optimizer shardings, activation
rules, XLA latency-hiding flags, checkpointing, and the training loop for
any assigned architecture:

    # real TPU pod (mesh axes map onto the physical slice):
    python -m repro.launch.train --arch qwen2-7b --steps 1000 \\
        --ckpt-dir gs://.../ckpts

    # CPU rehearsal on a debug mesh (forces fake host devices):
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
    PYTHONPATH=src python -m repro.launch.train --arch smollm-360m \\
        --debug-mesh 2x4 --reduce --steps 20
"""
import os

# overlap compute with collectives on real hardware (no-op on CPU); added
# to whatever LIBTPU_INIT_ARGS the machine already carries, never in its
# place
os.environ["LIBTPU_INIT_ARGS"] = " ".join(filter(None, (
    os.environ.get("LIBTPU_INIT_ARGS", ""),
    "--xla_tpu_enable_async_collective_fusion=true",
    "--xla_tpu_enable_latency_hiding_scheduler=true")))

import argparse  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=512)
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--save-every", type=int, default=50)
    ap.add_argument("--reduce", action="store_true",
                    help="use the reduced (smoke) config")
    ap.add_argument("--debug-mesh", default=None,
                    help="DxM, e.g. 2x4 (requires forced host devices)")
    ap.add_argument("--multi-pod", action="store_true")
    args = ap.parse_args()

    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.configs import get_config, reduced
    from repro.distributed.ctx import use_rules
    from repro.launch import use_compile_cache
    from repro.distributed.sharding import (activation_rules, batch_specs,
                                            param_specs)
    from repro.launch.mesh import make_debug_mesh, make_production_mesh
    from repro.models.lm import RunConfig
    from repro.optim.adamw import OptConfig
    from repro.train.loop import train
    from repro.train.step import init_train_state

    use_compile_cache()
    cfg = get_config(args.arch)
    if args.reduce:
        cfg = reduced(cfg)
    rc = RunConfig(q_chunk=0 if not args.reduce else 64, kv_chunk=512,
                   loss_chunk=512, remat=not args.reduce)
    opt = OptConfig(lr=args.lr, total_steps=args.steps,
                    warmup_steps=max(args.steps // 20, 1))

    mesh = None
    state_sh = batch_sh = rules = None
    if args.debug_mesh or len(jax.devices()) > 1:
        if args.debug_mesh:
            d, m = map(int, args.debug_mesh.split("x"))
            mesh = make_debug_mesh(d, m)
        else:
            mesh = make_production_mesh(multi_pod=args.multi_pod)
        ns = lambda t: jax.tree.map(
            lambda s: NamedSharding(mesh, s), t,
            is_leaf=lambda x: isinstance(x, P))
        abstract = jax.eval_shape(
            lambda: init_train_state(cfg, jax.random.key(0), rc))
        ps = param_specs(abstract["params"], cfg, mesh)
        state_sh = ns({"params": ps, "opt": {"m": ps, "v": ps, "step": P()}})
        bs = batch_specs(cfg, mesh, "train", args.batch,
                         microbatched=args.accum > 1)
        batch_sh = ns(bs)
        rules = activation_rules(cfg, mesh, "train", args.batch)

    def run():
        return train(cfg, rc, opt, steps=args.steps, batch=args.batch,
                     seq=args.seq, accum=args.accum, ckpt_dir=args.ckpt_dir,
                     save_every=args.save_every, mesh=mesh,
                     state_shardings=state_sh, batch_shardings=batch_sh)

    if mesh is not None:
        with jax.set_mesh(mesh), use_rules(mesh, rules):
            out = run()
    else:
        out = run()
    h = out["history"]
    print(f"done: ce {h[0]['ce']:.4f} -> {h[-1]['ce']:.4f}; "
          f"stragglers={len(out['stragglers'])}")


if __name__ == "__main__":
    main()
