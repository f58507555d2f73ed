"""Cross-subsystem differential fuzz suite (ISSUE 5 satellite).

PR 1-4 grew four orthogonal registries — schedule policy x executor x
quant scheme x serving path — that were only spot-checked at hand-picked
points.  This suite fuzzes the cross-product: hypothesis-driven draws over
(config shape x policy x executor x scheme x batch skew), asserting the
xla and pallas executors against the dense fp32 oracle within each
scheme's DECLARED ``rel_error_bound``, plus tight xla-vs-pallas agreement
on the SAME plan (routing built once, executed twice — so a top-k tie can
never make the comparison vacuous).

Runs under tests/hypothesis_compat.py: with hypothesis installed these
are real property tests (CI pins ``--hypothesis-seed=0``); without it the
shim replays a deterministic fixed-example set (REPRO_FUZZ_SEED /
REPRO_FUZZ_EXAMPLES).

Marked ``slow``: tier-1 (`pytest -q`, addopts ``-m "not slow"``) skips
this module; the CI ``fuzz`` stage runs it with the pinned seed.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis_compat import given, settings, st

from repro.core.dispatch import MoEDispatchConfig
from repro.execution import execute, plan_dispatch
from repro.kernels import ref
from repro.quantization import available_schemes, get_scheme
from repro.scheduling import available_policies, expert_capacity

pytestmark = pytest.mark.slow

# the independent numpy capacity-drop oracle lives with the policy tests
from test_scheduling_policies import expected_keep  # noqa: E402

# fp32 re-association floor: even the 'none' scheme (declared bound 0.0,
# bitwise through ONE backend) differs from the dense oracle by operation
# order; this matches the tolerance the hand-picked oracle tests use
FP_REORDER_FLOOR = 5e-4


@st.composite
def dispatch_cases(draw):
    E = draw(st.sampled_from([4, 8, 16]))
    return dict(
        T=draw(st.sampled_from([8, 24, 64])),
        E=E,
        k=draw(st.integers(1, min(4, E))),
        M=draw(st.sampled_from([8, 16])),
        d=draw(st.sampled_from([8, 16])),
        f=draw(st.sampled_from([16, 32])),
        # router-column skew: 0 = balanced, 2.0 = zipf-hot expert 0 —
        # drives the dynamic policy's adaptive blocks and real capacity
        # drops at small capacity factors
        alpha=draw(st.sampled_from([0.0, 1.2, 2.0])),
        policy=draw(st.sampled_from(sorted(available_policies()))),
        scheme=draw(st.sampled_from(available_schemes())),
        capacity_factor=draw(st.sampled_from([0.5, 1.25, 2.0])),
        fuse_gate_up=draw(st.booleans()),
        fold_combine=draw(st.booleans()),
        seed=draw(st.integers(0, 2 ** 16)),
    )


def _build(case):
    T, E, k, M, d, f = (case[x] for x in "TEkMdf")
    ks = jax.random.split(jax.random.key(case["seed"]), 5)
    x = jax.random.normal(ks[0], (T, d))
    wr = jax.random.normal(ks[1], (d, E)) * 0.3
    if case["alpha"] > 0:        # tilt routing mass toward low expert ids
        wr = wr + 2.0 * case["alpha"] * jnp.linspace(1.0, 0.0, E)[None, :]
    wg = jax.random.normal(ks[2], (E, d, f)) * 0.3
    wu = jax.random.normal(ks[3], (E, d, f)) * 0.3
    wd = jax.random.normal(ks[4], (E, f, d)) * 0.3
    cfg = MoEDispatchConfig(
        n_experts=E, top_k=k, block_m=M, executor="xla",
        schedule_policy=case["policy"],
        capacity_factor=case["capacity_factor"],
        fuse_gate_up=case["fuse_gate_up"],
        fold_combine=case["fold_combine"])
    return x, wr, wg, wu, wd, cfg


def _quantize_weights(wg, wu, wd, scheme):
    if scheme == "none":
        return {"w_gate": wg, "w_up": wu, "w_down": wd}
    sch = get_scheme(scheme)
    return {"w_gate": sch.quantize(wg), "w_up": sch.quantize(wu),
            "w_down": sch.quantize(wd)}


def _oracle(x, wg, wu, wd, plan, cfg):
    """Dense fp32 oracle on the plan's routing, with capacity-policy drops
    zeroed exactly as the bucket overflow rule prescribes."""
    weights, indices = plan.weights, plan.indices
    if cfg.schedule_policy == "capacity_factor":
        T, k = indices.shape
        cap = expert_capacity(T, k, cfg.n_experts, cfg.block_m,
                              cfg.capacity_factor)
        keep = expected_keep(np.asarray(indices), cap)
        weights = jnp.where(jnp.asarray(keep), weights, 0.0)
    return ref.moe_ffn_dense_ref(x, wg, wu, wd, weights, indices)


@given(dispatch_cases())
@settings(max_examples=30, deadline=None)
def test_fuzz_executor_x_policy_x_scheme_vs_dense_oracle(case):
    """ONE plan, BOTH in-scan executors, every scheme: each backend stays
    inside the scheme's declared rel_error_bound of the fp32 dense oracle,
    and the two backends agree tightly with each other (same routing, same
    schedule, same dequantized blocks — only GEMM order differs)."""
    x, wr, wg, wu, wd, cfg = _build(case)
    plan = plan_dispatch(x, wr, cfg, with_schedule=True)
    w = _quantize_weights(wg, wu, wd, case["scheme"])
    oracle = _oracle(x, wg, wu, wd, plan, cfg)
    scale = float(jnp.max(jnp.abs(oracle))) or 1.0
    bound = max(get_scheme(case["scheme"]).rel_error_bound,
                FP_REORDER_FLOOR)

    outs = {}
    for executor in ("xla", "pallas"):
        y = execute(plan, x, w, cfg, executor=executor)
        rel = float(jnp.max(jnp.abs(y - oracle))) / scale
        assert rel <= bound, (case, executor, rel, bound)
        outs[executor] = y
    cross = float(jnp.max(jnp.abs(outs["xla"] - outs["pallas"]))) / scale
    assert cross <= FP_REORDER_FLOOR, (case, cross)


@given(dispatch_cases())
@settings(max_examples=15, deadline=None)
def test_fuzz_policies_agree_when_nothing_drops(case):
    """Differential across SCHEDULE POLICIES: on the same routing, any
    two drop-free policies are just different padded layouts of the same
    math — outputs must agree to fp reorder tolerance."""
    x, wr, wg, wu, wd, cfg = _build(case)
    w = {"w_gate": wg, "w_up": wu, "w_down": wd}
    ys = []
    for policy in ("fixed", "dynamic"):
        c = cfg._replace(schedule_policy=policy)
        plan = plan_dispatch(x, wr, c, with_schedule=True)
        ys.append(execute(plan, x, w, c))
    scale = float(jnp.max(jnp.abs(ys[0]))) or 1.0
    diff = float(jnp.max(jnp.abs(ys[0] - ys[1]))) / scale
    assert diff <= FP_REORDER_FLOOR, (case, diff)


@given(dispatch_cases())
@settings(max_examples=10, deadline=None)
def test_fuzz_in_scan_dequant_matches_materialized(case):
    """Differential across WEIGHT REPRESENTATIONS: executing a plan on
    compressed weights (per-block in-scan dequant) must be BITWISE equal
    to materializing the dense stack first — on fuzzed shapes, not just
    the hand-picked ones in test_quantization.py."""
    if case["scheme"] == "none":
        return
    x, wr, wg, wu, wd, cfg = _build(case)
    plan = plan_dispatch(x, wr, cfg, with_schedule=True)
    w = _quantize_weights(wg, wu, wd, case["scheme"])
    w_mat = {k: v.materialize() for k, v in w.items()}
    for executor in ("xla", "pallas"):
        y_lazy = execute(plan, x, w, cfg, executor=executor)
        y_mat = execute(plan, x, w_mat, cfg, executor=executor)
        np.testing.assert_array_equal(np.asarray(y_lazy), np.asarray(y_mat),
                                      err_msg=str((case, executor)))


@st.composite
def serve_cases(draw):
    return dict(
        policy=draw(st.sampled_from(["fixed", "dynamic"])),
        scheme=draw(st.sampled_from(["none", "int8_expert"])),
        block=draw(st.sampled_from([4, 8])),
        chunk=draw(st.integers(2, 8)),
        prefix_cache=draw(st.booleans()),
        seed=draw(st.integers(0, 2 ** 16)),
    )


@given(serve_cases())
@settings(max_examples=5, deadline=None)
def test_fuzz_serving_paged_equals_contiguous(case):
    """End-to-end serving differential: greedy tokens through the PAGED
    engine (fuzzed block size / chunk size / prefix caching) equal the
    contiguous engine's under fuzzed policy x scheme — the cache layout
    must never reach the sampled tokens."""
    from repro.configs import get_config, reduced
    from repro.models import RunConfig, init_params
    from repro.serve.engine import Request, ServeEngine
    cfg = reduced(get_config("moonshot-v1-16b-a3b"), layers=2, d_model=32,
                  vocab=128)
    params = init_params(cfg, jax.random.key(0))
    rc = RunConfig(q_chunk=16, kv_chunk=16, schedule_policy=case["policy"],
                   quant=case["scheme"], moe_stats=True)
    rng = np.random.default_rng(case["seed"])
    shared = rng.integers(0, cfg.vocab_size, 6).astype(np.int32)

    def mk():
        return [Request(rid=i,
                        prompt=np.concatenate(
                            [shared, rng.integers(0, cfg.vocab_size, 1 + i)]
                        ).astype(np.int32), max_new=4)
                for i in range(3)]

    rng_state = rng.bit_generator.state
    ref_reqs = mk()
    ServeEngine(cfg, params, slots=2, capacity=32, rc=rc,
                kv_block_size=0).run(ref_reqs)
    rng.bit_generator.state = rng_state
    paged_reqs = mk()
    eng = ServeEngine(cfg, params, slots=2, capacity=32, rc=rc,
                      kv_block_size=case["block"],
                      prefill_chunk=case["chunk"],
                      prefix_cache=case["prefix_cache"])
    eng.run(paged_reqs)
    assert [r.out for r in paged_reqs] == [r.out for r in ref_reqs], case


# ----------------------------------------------------------------------
# Sharded EP vs single-device dispatch (policy x scheme x skew fuzz)
# ----------------------------------------------------------------------
def test_fuzz_sharded_ep_matches_single_device():
    """Padding-free sharded EP == single-device dispatch over seeded
    (policy x quant-scheme x router-skew) draws, including the drop
    regime: the capacity_factor policy's drop SET must reproduce the
    single-device first-come-first-kept order exactly, whatever dim the
    tokens were split on.  One subprocess (8 forced host devices) loops
    all draws; the cross-layout bound is the fp-reorder floor since both
    sides run the identical (de)quantized weights."""
    import pathlib
    import subprocess
    import sys

    src = str(pathlib.Path(__file__).resolve().parents[1] / "src")
    code = """
import numpy as np, jax, jax.numpy as jnp
from repro.core import apply_moe, dispatch_config, init_moe_params
from repro.configs.base import MoEConfig
from repro.core.distributed import apply_moe_ep
from repro.launch.mesh import make_debug_mesh
from repro.quantization import quantize_moe_params

POLICIES = ("fixed", "dynamic", "capacity_factor")
SCHEMES = ("none", "int8_expert", "int4_packed")
ALPHAS = (0.0, 1.2, 2.0)     # router-skew: uniform .. zipf2.0 stress
saw_drops = 0
rng = np.random.default_rng(0)
for draw in range(6):
    pol = POLICIES[draw % 3]
    sch = SCHEMES[int(rng.integers(3))]
    alpha = ALPHAS[int(rng.integers(3))]
    B, S, d = int(rng.integers(1, 5)), 32, 16
    moe = MoEConfig(n_experts=8, top_k=2, d_ff_expert=32, block_m=8,
                    capacity_factor=float(rng.choice([0.5, 1.0])))
    params = init_moe_params(jax.random.key(draw), moe, d)
    # zipf-scaled router columns concentrate routing mass on low experts
    f = (np.arange(moe.n_experts) + 1.0) ** (-alpha)
    params["router"] = params["router"] * jnp.asarray(
        3.0 * f / f.mean(), params["router"].dtype)
    if sch != "none":
        params = quantize_moe_params(params, sch)
    x = jax.random.normal(jax.random.key(100 + draw), (B, S, d))
    # capacity semantics are per data shard -> data=1 for the drop cells
    mesh = make_debug_mesh(data=1 if pol == "capacity_factor" else 2,
                           model=4)
    dcfg = dispatch_config(moe, executor="xla", schedule_policy=pol,
                           emit_stats=True)
    y_ref, aux_ref = apply_moe(params, x, dcfg)
    with jax.set_mesh(mesh):
        y_ep, aux_ep = jax.jit(lambda p, x: apply_moe_ep(
            p, x, dcfg))(params, x)
    np.testing.assert_allclose(
        np.asarray(y_ep), np.asarray(y_ref), rtol=5e-4, atol=5e-4,
        err_msg=f"draw={draw} pol={pol} scheme={sch} alpha={alpha}")
    assert float(aux_ep["sched/dropped_rows"]) \
        == float(aux_ref["sched/dropped_rows"]), (draw, pol, sch, alpha)
    saw_drops += float(aux_ref["sched/dropped_rows"]) > 0
assert saw_drops > 0, "fuzz must exercise the drop regime at least once"
print("OK drops_in", saw_drops, "draws")
"""
    env = {"XLA_FLAGS": "--xla_force_host_platform_device_count=8",
           "PYTHONPATH": src, "PATH": "/usr/bin:/bin",
           "HOME": "/root", "JAX_PLATFORMS": "cpu"}
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=900)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    assert "OK" in r.stdout
