"""Multi-process serving smoke: 2-process ``jax.distributed`` launch with
a single-process forced-device-count fallback.

The real thing first: two subprocesses join a coordination group
(process 0 binds the coordinator) and run the distributed serving
launcher.  On backends without multi-process compute (CPU: the
coordination service and global device visibility work, but jit dispatch
across processes does not) the launcher exits with its documented
capability message — that counts as "coordination verified, compute
unsupported" and the smoke falls back to the single-process path the
ISSUE's CI job allows: one process, ``--ep-devices N`` forcing a
multi-device host mesh, same per-host admission + global-step code.

Either way the smoke FAILS unless a distributed serve run completes all
its requests.

This is a CPU-only tool: every child runs with ``JAX_PLATFORMS=cpu``.
A chip belongs to one process at a time, so children that each opened
the accelerator would fail or hang; the chip's own EP path is
``chip_smoke.py --chips 4``, which drives all devices from one process.

Usage:
    PYTHONPATH=src python tools/mp_serve_smoke.py [--processes 2]
        [--port 12377]
"""
from __future__ import annotations

import argparse
import os
import subprocess
import sys

LAUNCH = [sys.executable, "-m", "repro.launch.serve",
          "--arch", "moonshot-v1-16b-a3b", "--reduce",
          "--requests", "3", "--max-new", "3", "--distributed"]


def _env():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = "src" + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    return env


def try_multiprocess(n: int, port: int) -> bool:
    """True iff the n-process launch served its requests end to end."""
    procs = [subprocess.Popen(
        LAUNCH + ["--coordinator", f"localhost:{port}",
                  "--num-processes", str(n), "--process-id", str(i)],
        env=_env(), stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True) for i in range(n)]
    outs = []
    for p in procs:
        try:
            out, _ = p.communicate(timeout=420)
        except subprocess.TimeoutExpired:
            p.kill()
            out, _ = p.communicate()
        outs.append(out)
    ok = all(p.returncode == 0 for p in procs) \
        and "requests completed" in outs[0]
    unsupported = any("cannot run multi-process computations" in o
                      for o in outs)
    print(f"multi-process launch: "
          f"{'OK' if ok else 'unsupported' if unsupported else 'FAILED'}")
    if not ok and not unsupported:
        for i, o in enumerate(outs):
            print(f"--- process {i} output ---\n{o}")
    return ok


def single_process_fallback() -> None:
    out = subprocess.run(
        LAUNCH + ["--ep-devices", "2", "--hosts", "2"],
        env=_env(), stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True, timeout=420)
    sys.stdout.write(out.stdout)
    assert out.returncode == 0, "fallback distributed serve launch failed"
    assert "3/3 requests completed" in out.stdout, \
        "distributed serve smoke did not complete all requests"
    print("single-process fallback (forced 2-device mesh, 2 host "
          "queues): OK")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--processes", type=int, default=2)
    ap.add_argument("--port", type=int, default=12377)
    args = ap.parse_args()
    if not try_multiprocess(args.processes, args.port):
        single_process_fallback()
    print("mp serve smoke OK")


if __name__ == "__main__":
    main()
