"""Multi-device tests — each case runs in a SUBPROCESS with
--xla_force_host_platform_device_count=8 so the main pytest process keeps
the plain 1-device CPU (per the dry-run isolation requirement)."""
import json
import pathlib
import subprocess
import sys

import pytest

SRC = str(pathlib.Path(__file__).resolve().parents[1] / "src")


def run_sub(code: str):
    env = {"XLA_FLAGS": "--xla_force_host_platform_device_count=8",
           "PYTHONPATH": SRC, "PATH": "/usr/bin:/bin",
           "HOME": "/root", "JAX_PLATFORMS": "cpu"}
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=900)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    return r.stdout


def test_ep_dispatch_matches_single_device():
    run_sub("""
import numpy as np, jax, jax.numpy as jnp
from repro.core import apply_moe, dispatch_config, init_moe_params
from repro.core.distributed import apply_moe_ep
from repro.configs.base import MoEConfig
from repro.launch.mesh import make_debug_mesh
mesh = make_debug_mesh(data=2, model=4)
moe = MoEConfig(n_experts=8, top_k=2, d_ff_expert=32, n_shared_experts=1, block_m=8)
params = init_moe_params(jax.random.key(0), moe, 16)
x = jax.random.normal(jax.random.key(1), (4, 32, 16))
dcfg = dispatch_config(moe, executor="xla")
y_ref, _ = apply_moe(params, x, dcfg)
with jax.set_mesh(mesh):
    y_ep, _ = jax.jit(lambda p, x: apply_moe_ep(p, x, dcfg, capacity_factor=8.0))(params, x)
    y_r, _ = jax.jit(lambda p, x: apply_moe_ep(p, x, dcfg, token_layout="replicated"))(params, x)
np.testing.assert_allclose(np.asarray(y_ep), np.asarray(y_ref), rtol=2e-4, atol=2e-4)
np.testing.assert_allclose(np.asarray(y_r), np.asarray(y_ref), rtol=2e-4, atol=2e-4)
print("OK")
""")


def test_ep_capacity_drops_tokens_deterministically():
    run_sub("""
import numpy as np, jax, jax.numpy as jnp
from repro.core import dispatch_config, init_moe_params
from repro.core.distributed import apply_moe_ep
from repro.configs.base import MoEConfig
from repro.launch.mesh import make_debug_mesh
mesh = make_debug_mesh(data=1, model=4)
moe = MoEConfig(n_experts=4, top_k=1, d_ff_expert=16, block_m=8)
params = init_moe_params(jax.random.key(0), moe, 8)
x = jax.random.normal(jax.random.key(1), (1, 64, 8))
# drops belong to the capacity_factor POLICY now; fixed/dynamic never drop
# under the padding-free sharded layout (they never dropped single-device)
dcfg = dispatch_config(moe, executor="xla",
                       schedule_policy="capacity_factor")
with jax.set_mesh(mesh):
    tight, _ = jax.jit(lambda p, x: apply_moe_ep(p, x, dcfg, capacity_factor=0.25))(params, x)
    loose, _ = jax.jit(lambda p, x: apply_moe_ep(p, x, dcfg, capacity_factor=8.0))(params, x)
t, l = np.asarray(tight), np.asarray(loose)
dropped_rows = (np.abs(t).sum(-1) == 0).sum()
assert dropped_rows > 0, "tight capacity must drop some tokens"
# run twice -> identical (deterministic drop policy: lowest slot wins)
with jax.set_mesh(mesh):
    tight2, _ = jax.jit(lambda p, x: apply_moe_ep(p, x, dcfg, capacity_factor=0.25))(params, x)
np.testing.assert_array_equal(t, np.asarray(tight2))
print("OK", int(dropped_rows))
""")


def test_ep_replicated_schedule_policies_match_single_device():
    """capacity_factor / dynamic policies under EP replicated dispatch ==
    the same policy on a single device (global-capacity drop semantics)."""
    run_sub("""
import numpy as np, jax, jax.numpy as jnp
from repro.core import apply_moe, dispatch_config, init_moe_params
from repro.configs.base import MoEConfig
from repro.core.distributed import apply_moe_ep
from repro.launch.mesh import make_debug_mesh
mesh = make_debug_mesh(data=1, model=4)
moe = MoEConfig(n_experts=8, top_k=2, d_ff_expert=32, block_m=8,
                capacity_factor=0.5)
params = init_moe_params(jax.random.key(0), moe, 16)
x = jax.random.normal(jax.random.key(1), (1, 64, 16))
for pol in ("capacity_factor", "dynamic"):
    dcfg = dispatch_config(moe, executor="xla", schedule_policy=pol)
    y_ref, _ = apply_moe(params, x, dcfg)
    if pol == "capacity_factor":
        assert float(jnp.max(jnp.abs(
            y_ref - apply_moe(params, x, dcfg._replace(executor="dense"))[0]
        ))) > 1e-6, "cf=0.5 must actually drop tokens"
    with jax.set_mesh(mesh):
        y_r, _ = jax.jit(lambda p, x: apply_moe_ep(
            p, x, dcfg, token_layout="replicated"))(params, x)
    np.testing.assert_allclose(np.asarray(y_r), np.asarray(y_ref),
                               rtol=2e-4, atol=2e-4)
print("OK")
""")


def test_ep_gathers_compressed_bytes_for_every_scheme():
    """Quantized expert params flow through BOTH EP layouts for every
    registered scheme (not just int8): the shard_map partition specs are
    built per leaf, so a QuantTensor's compressed payload + scales shard
    over the EP axis and each rank dequantizes only its own experts'
    blocks.  Output must match the single-device quantized run exactly."""
    run_sub("""
import numpy as np, jax, jax.numpy as jnp
from repro.core import apply_moe, dispatch_config, init_moe_params
from repro.core.distributed import apply_moe_ep
from repro.configs.base import MoEConfig
from repro.launch.mesh import make_debug_mesh
from repro.quantization import quantize_moe_params

mesh = make_debug_mesh(data=2, model=4)
moe = MoEConfig(n_experts=8, top_k=2, d_ff_expert=32, block_m=8)
params = init_moe_params(jax.random.key(0), moe, 16)
x = jax.random.normal(jax.random.key(1), (4, 32, 16))
for sch in ("int8_expert", "int8_channel", "int4_packed"):
    qp = quantize_moe_params(params, sch)
    dcfg = dispatch_config(moe, executor="xla")
    y_ref, _ = apply_moe(qp, x, dcfg)
    with jax.set_mesh(mesh):
        y_sh, _ = jax.jit(lambda p, x: apply_moe_ep(
            p, x, dcfg, capacity_factor=8.0))(qp, x)
        y_r, _ = jax.jit(lambda p, x: apply_moe_ep(
            p, x, dcfg, token_layout="replicated"))(qp, x)
    np.testing.assert_allclose(np.asarray(y_sh), np.asarray(y_ref),
                               rtol=1e-6, atol=1e-6, err_msg=sch)
    np.testing.assert_allclose(np.asarray(y_r), np.asarray(y_ref),
                               rtol=1e-6, atol=1e-6, err_msg=sch)
print("OK")
""")


def test_full_model_sharded_train_step_matches_single_device():
    """qwen2 reduced: jitted sharded train step on a 2x4 mesh == unsharded."""
    run_sub("""
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import get_config, reduced
from repro.models import RunConfig
from repro.train.step import init_train_state, make_train_step
from repro.optim.adamw import OptConfig
from repro.data.pipeline import make_batch
from repro.launch.mesh import make_debug_mesh
from repro.distributed.sharding import param_specs, batch_specs
from repro.distributed.ctx import use_rules
from repro.distributed.sharding import activation_rules

cfg = reduced(get_config("qwen2-7b"), layers=2, d_model=64, n_heads=4)
rc = RunConfig(q_chunk=0, kv_chunk=16, loss_chunk=16)
opt = OptConfig(lr=1e-3, warmup_steps=1, total_steps=10, weight_decay=0.0)
state = init_train_state(cfg, jax.random.key(0), rc)
batch = make_batch(cfg, 8, 32, step=0)

s_ref, m_ref = jax.jit(make_train_step(cfg, rc, opt, 1))(state, batch)

mesh = make_debug_mesh(data=2, model=4)
ps = param_specs(state["params"], cfg, mesh)
ss = {"params": ps, "opt": {"m": ps, "v": ps, "step": P()}}
bs = batch_specs(cfg, mesh, "train", 8)
ns = lambda t: jax.tree.map(lambda s: NamedSharding(mesh, s), t,
                            is_leaf=lambda x: isinstance(x, P))
with jax.set_mesh(mesh), use_rules(mesh, activation_rules(cfg, mesh, "train", 8)):
    f = jax.jit(make_train_step(cfg, rc, opt, 1),
                in_shardings=(ns(ss), ns(bs)), out_shardings=(ns(ss), None))
    s_sh, m_sh = f(jax.device_put(state, ns(ss)),
                   {k: jax.device_put(v, ns(bs)[k]) for k, v in batch.items()})
assert abs(float(m_ref["loss"]) - float(m_sh["loss"])) < 1e-4
d = jax.tree.map(lambda a, b: float(jnp.max(jnp.abs(a - b))),
                 s_ref["params"], jax.device_get(s_sh["params"]))
assert max(jax.tree.leaves(d)) < 1e-4, max(jax.tree.leaves(d))
print("OK")
""")


def test_elastic_restore_to_different_mesh(tmp_path):
    """Checkpoint on 1 device -> restore sharded on 8 (elastic re-shard)."""
    run_sub(f"""
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.checkpoint.manager import CheckpointManager
from repro.launch.mesh import make_debug_mesh
m = CheckpointManager(r"{tmp_path}", async_save=False)
state = {{"w": jnp.arange(32.0).reshape(8, 4), "step": jnp.int32(7)}}
m.save(7, state)
mesh = make_debug_mesh(data=2, model=4)
sh = {{"w": NamedSharding(mesh, P("data", "model")),
      "step": NamedSharding(mesh, P())}}
restored = m.restore(state, shardings=sh)
assert restored["w"].sharding.spec == P("data", "model")
np.testing.assert_allclose(np.asarray(restored["w"]), np.asarray(state["w"]))
print("OK")
""")


def test_compressed_psum_pod_axis():
    run_sub("""
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.optim.compress import compressed_psum
from repro.launch.mesh import make_debug_mesh
mesh = make_debug_mesh(data=1, model=1, pod=8)
g = jax.random.normal(jax.random.key(0), (8, 64))
def body(gl):
    return compressed_psum(gl[0], "pod")[None]
with jax.set_mesh(mesh):
    out = jax.jit(jax.shard_map(body, mesh=mesh,
        in_specs=P("pod", None), out_specs=P("pod", None),
        check_vma=False))(g)
ref = jnp.sum(g, 0)
got = np.asarray(out)[0]
rel = np.abs(got - np.asarray(ref)).max() / (np.abs(np.asarray(ref)).max())
assert rel < 2e-2, rel   # int8 quantization tolerance
print("OK", rel)
""")


def test_flash_decode_shard_map_combine():
    """Explicit shard_map LSE combine over seq-sharded KV == full attn."""
    run_sub("""
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.models.attention import flash_attention, combine_stats, naive_attention
from repro.launch.mesh import make_debug_mesh
mesh = make_debug_mesh(data=2, model=4)
B, S, H, D = 4, 64, 4, 16
ks = jax.random.split(jax.random.key(0), 3)
q = jax.random.normal(ks[0], (B, 1, H, D))
k = jax.random.normal(ks[1], (B, S, H, D))
v = jax.random.normal(ks[2], (B, S, H, D))
pos = jnp.int32(S - 1)
def local(q, k, v):
    idx = jax.lax.axis_index("model")
    off = idx * k.shape[1]
    acc, l, m = flash_attention(q, k, v, causal=False, kv_limit=pos,
                                kv_offset=off, q_chunk=1, kv_chunk=16,
                                return_stats=True)
    out = combine_stats(acc, l, m, "model")
    return jnp.moveaxis(out, 3, 1).reshape(q.shape[0], 1, -1, out.shape[-1])
with jax.set_mesh(mesh):
    f = jax.jit(jax.shard_map(local, mesh=mesh,
        in_specs=(P("data", None, None, None), P("data", "model", None, None),
                  P("data", "model", None, None)),
        out_specs=P("data", None, None, None), check_vma=False))
    out = f(q, k, v)
ref = naive_attention(q, k, v, causal=False, kv_limit=pos)
np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=3e-5, atol=3e-5)
print("OK")
""")


# ----------------------------------------------------------------------
# Padding-free sharded EP (ISSUE 10): policy-honoring dispatch
# ----------------------------------------------------------------------
def test_ep_sharded_policies_match_single_device_with_drops():
    """Every schedule policy produces the SAME outputs, drop set, and
    ScheduleStats under the padding-free sharded layout, the overlapped
    variant, and the replicated layout as on a single device — including
    the capacity_factor drop regime (cf=0.5 drops half the assignments).
    fixed/dynamic use a token-sharded 2x4 mesh; the capacity cell uses
    data=1 (capacity semantics are per data shard, matching GShard)."""
    run_sub("""
import numpy as np, jax, jax.numpy as jnp
from repro.core import apply_moe, dispatch_config, init_moe_params
from repro.configs.base import MoEConfig
from repro.core.distributed import apply_moe_ep
from repro.launch.mesh import make_debug_mesh
moe = MoEConfig(n_experts=8, top_k=2, d_ff_expert=32, block_m=8,
                capacity_factor=0.5)
params = init_moe_params(jax.random.key(0), moe, 16)
x = jax.random.normal(jax.random.key(1), (4, 32, 16))
for pol in ("fixed", "dynamic", "capacity_factor"):
    mesh = make_debug_mesh(data=1 if pol == "capacity_factor" else 2,
                           model=4)
    dcfg = dispatch_config(moe, executor="xla", schedule_policy=pol,
                           emit_stats=True)
    y_ref, aux_ref = apply_moe(params, x, dcfg)
    with jax.set_mesh(mesh):
        run = lambda **kw: jax.jit(lambda p, x: apply_moe_ep(
            p, x, dcfg, **kw))(params, x)
        y_sh, aux_sh = run()
        y_ov, aux_ov = run(overlap=2)
        y_rp, aux_rp = run(token_layout="replicated")
    for tag, y in (("sharded", y_sh), ("overlap", y_ov),
                   ("replicated", y_rp)):
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                                   rtol=5e-4, atol=5e-4,
                                   err_msg=f"{tag} {pol}")
    # stats parity: drops + useful rows are GLOBAL totals = single-device
    for k in ("sched/dropped_rows", "sched/useful_rows"):
        ref_v = float(aux_ref[k])
        for tag, aux in (("sharded", aux_sh), ("overlap", aux_ov),
                         ("replicated", aux_rp)):
            assert float(aux[k]) == ref_v, (pol, tag, k, float(aux[k]),
                                            ref_v)
    if pol == "capacity_factor":
        assert float(aux_sh["sched/dropped_rows"]) > 0, \
            "cf=0.5 cell must exercise the drop regime"
print("OK")
""")


def test_ep_overlap_token_identical_to_non_overlapped():
    """The overlapped dispatch is token-identical to the non-overlapped
    path on the same mesh (full-batch routing + drop decisions are made
    BEFORE chunking), and overlap=0 goes down the literal n_micro=1
    straight-line code path."""
    run_sub("""
import numpy as np, jax, jax.numpy as jnp
from repro.core import dispatch_config, init_moe_params
from repro.configs.base import MoEConfig
from repro.core.distributed import apply_moe_ep
from repro.launch.mesh import make_debug_mesh
mesh = make_debug_mesh(data=2, model=4)
moe = MoEConfig(n_experts=8, top_k=2, d_ff_expert=32, block_m=8,
                capacity_factor=0.5)
params = init_moe_params(jax.random.key(0), moe, 16)
x = jax.random.normal(jax.random.key(1), (4, 32, 16))
for pol in ("fixed", "dynamic", "capacity_factor"):
    dcfg = dispatch_config(moe, executor="xla", schedule_policy=pol)
    with jax.set_mesh(mesh):
        y0, _ = jax.jit(lambda p, x: apply_moe_ep(p, x, dcfg))(params, x)
        for n_micro in (2, 4):
            y1, _ = jax.jit(lambda p, x, n=n_micro: apply_moe_ep(
                p, x, dcfg, overlap=n))(params, x)
            np.testing.assert_allclose(
                np.asarray(y1), np.asarray(y0), rtol=1e-6, atol=1e-6,
                err_msg=f"{pol} n_micro={n_micro}")
print("OK")
""")


def test_ep_serve_engine_counts_dropped_tokens():
    """EP serving surfaces dispatch drops: with moe_stats on, retired
    requests carry the ``sched/*`` keys and the obs registry exposes the
    ``serve/ep_dropped_tokens`` counter (satellite: the skew table stays
    honest under EP)."""
    run_sub("""
import numpy as np, jax
from repro.configs import get_config, reduced
from repro.models import RunConfig
from repro.obs import Observability
from repro.serve.engine import Request, ServeEngine
from repro.serve.distributed import DistributedServeLoop
from repro.launch.mesh import make_ep_mesh
cfg = reduced(get_config("moonshot-v1-16b-a3b"))
from repro.models import init_params
params = init_params(cfg, jax.random.key(0))
rc = RunConfig(q_chunk=64, kv_chunk=64, ep=True, moe_stats=True,
               schedule_policy="capacity_factor", capacity_factor=0.5)
obs = Observability.memory()
with jax.set_mesh(make_ep_mesh(2)):
    eng = ServeEngine(cfg, params, slots=2, capacity=32, rc=rc, obs=obs)
    rng = np.random.default_rng(0)
    reqs = [Request(rid=i, prompt=rng.integers(0, cfg.vocab_size, 5)
                    .astype(np.int32), max_new=3) for i in range(3)]
    done = DistributedServeLoop(eng, n_hosts=2).run(reqs, max_steps=64)
assert len(done) == 3, [r.done for r in reqs]
for r in done:
    assert "sched/dropped_rows" in r.stats, sorted(r.stats)
names = {c["name"] for c in obs.metrics.snapshot()["counters"]}
assert "serve/ep_dropped_tokens" in names, sorted(names)
print("OK")
""")


def test_distributed_serve_loop_matches_engine_run():
    """Single-host sanity (no mesh): the per-host admission loop with
    n_hosts=1 completes the same request set as ServeEngine.run, and the
    round-robin partition is deterministic."""
    import numpy as np

    from repro.configs import get_config, reduced
    from repro.models import RunConfig, init_params
    from repro.serve.distributed import (DistributedServeLoop,
                                         partition_requests)
    from repro.serve.engine import Request, ServeEngine

    cfg = reduced(get_config("moonshot-v1-16b-a3b"))
    params = init_params(cfg, None or __import__("jax").random.key(0))
    rng = np.random.default_rng(0)

    def mk_reqs():
        return [Request(rid=i, prompt=np.arange(3 + i % 2,
                                                dtype=np.int32),
                        max_new=3) for i in range(4)]

    rc = RunConfig(q_chunk=64, kv_chunk=64)
    ref = ServeEngine(cfg, params, slots=2, capacity=32, rc=rc) \
        .run(mk_reqs(), max_steps=64)
    reqs = mk_reqs()
    eng = ServeEngine(cfg, params, slots=2, capacity=32, rc=rc)
    done = DistributedServeLoop(eng, n_hosts=2).run(reqs, max_steps=64)
    assert len(done) == len(ref) == 4
    assert {r.rid: r.out for r in done} == {r.rid: r.out for r in ref}

    parts = partition_requests(reqs, 3)
    assert [len(p) for p in parts] == [2, 1, 1]
    assert [r.rid for r in parts[0]] == [0, 3]
    import pytest
    with pytest.raises(ValueError):
        partition_requests(reqs, 0)


def test_static_schedule_alignment_guard():
    """_static_schedule refuses unaligned capacities loudly instead of
    silently misassigning block_expert (satellite bugfix)."""
    from repro.core.distributed import _static_schedule

    s = _static_schedule(32, 4, 8, 8)             # aligned: fine
    assert int(s.capacity) == 32
    with pytest.raises(ValueError, match="block_m-aligned"):
        _static_schedule(36, 4, 8, 9)             # 9 % 8 != 0
    with pytest.raises(ValueError, match="block_m-aligned"):
        _static_schedule(34, 2, 8, 16)            # rows 34 % 8 != 0


def test_capacity_factor_resolution_order():
    """apply_moe_ep resolves capacity headroom as
    ``explicit arg > cfg.capacity_factor`` — the ONE documented order
    (satellite bugfix: removes the PR 1 'pass 2.0 explicitly' footgun)."""
    from repro.configs.base import MoEConfig
    from repro.core import dispatch_config
    from repro.core.distributed import _resolve_capacity_factor

    moe = MoEConfig(n_experts=8, top_k=2, d_ff_expert=32, block_m=8,
                    capacity_factor=1.5)
    cfg = dispatch_config(moe, executor="xla")
    assert cfg.capacity_factor == 1.5             # defaulted from MoEConfig
    assert _resolve_capacity_factor(cfg, None) == 1.5
    assert _resolve_capacity_factor(cfg, 0.25) == 0.25
    cfg2 = dispatch_config(moe, executor="xla", capacity_factor=3.0)
    assert _resolve_capacity_factor(cfg2, None) == 3.0


def test_ep_param_specs_split_only_routed_expert_stacks():
    """The EP serving layout: each routed-expert stack is split on its
    expert dim (after the layer-stack dim when stacked), everything else
    (router, shared experts, attention, embeddings) is replicated."""
    import jax
    from jax.sharding import PartitionSpec as P
    from repro.configs import get_config, reduced
    from repro.distributed.sharding import ep_param_specs
    from repro.models import init_params
    cfg = reduced(get_config("moonshot-v1-16b-a3b")).replace(n_layers=3)
    shapes = jax.eval_shape(lambda: init_params(cfg, jax.random.key(0)))
    specs = ep_param_specs(shapes, cfg, axis="model")
    moe = specs["body"]["b0"]["moe"]
    for name in ("w_gate", "w_up", "w_down"):
        assert moe[name] == P(None, "model"), (name, moe[name])
        assert moe["shared"][name] == P(), name
    assert moe["router"] == P()
    assert specs["embed"] == P() and specs["head"] == P()
    assert specs["prefix"][0]["ffn"]["w_gate"] == P()
    split = [s for s in jax.tree.leaves(
        specs, is_leaf=lambda x: isinstance(x, P)) if s != P()]
    assert len(split) == 3, split
