"""Roofline methodology validation.

1. HLO collective parser unit tests on known synthetic HLO lines.
2. The scan-undercount premise: cost_analysis counts a scan body once.
3. Analytic FLOP model vs an UNROLLED compile of a reduced arch (the
   analytic numbers drive EXPERIMENTS.md §Roofline; this pins them to
   XLA's own counting within tolerance).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest


from repro.analysis.hlo import collective_report, parse_collectives


SYNTH = """
ENTRY %main.1 (p0: f32[16,16]) -> f32[16,16] {
  %ag = bf16[64,128]{1,0} all-gather(%x), channel_id=1, replica_groups=[16,16]<=[256], dimensions={0}, metadata={op_name="jit(f)/while/body/jvp(layer_stack)/dot"}
  %ar = f32[32,32]{1,0} all-reduce(%y), channel_id=2, replica_groups=[4,4]<=[16], metadata={op_name="jit(f)/opt"}
  %rs = f32[8,8]{1,0} reduce-scatter(%z), channel_id=3, replica_groups=[2,8]<=[16], dimensions={0}
  %a2a = bf16[4,16]{1,0} all-to-all(%w), channel_id=4, replica_groups=[1,16]<=[16], dimensions={0}
  %cp = f32[10]{0} collective-permute(%v), channel_id=5, source_target_pairs={{0,1}}
}
"""


def test_parser_link_byte_formulas():
    ops = parse_collectives(SYNTH)
    by = {o.kind: o for o in ops}
    assert by["all-gather"].result_bytes == 64 * 128 * 2
    assert by["all-gather"].group_size == 16
    np.testing.assert_allclose(by["all-gather"].link_bytes,
                               64 * 128 * 2 * 15 / 16)
    np.testing.assert_allclose(by["all-reduce"].link_bytes,
                               2 * 32 * 32 * 4 * 3 / 4)
    np.testing.assert_allclose(by["reduce-scatter"].link_bytes,
                               8 * 8 * 4 * 8 * 7 / 8)
    np.testing.assert_allclose(by["all-to-all"].link_bytes,
                               4 * 16 * 2 * 15 / 16)
    np.testing.assert_allclose(by["collective-permute"].link_bytes, 40)


def test_parser_loop_multipliers():
    rep = collective_report(SYNTH, layer_trips=10, accum_trips=3)
    ops = parse_collectives(SYNTH)
    ag = next(o for o in ops if o.kind == "all-gather")
    # the all-gather is inside layer_stack AND the accum while
    assert rep["by_kind"]["all-gather"] == ag.link_bytes * 30
    # the optimizer all-reduce is outside both loops
    ar = next(o for o in ops if o.kind == "all-reduce")
    assert rep["by_kind"]["all-reduce"] == ar.link_bytes


def test_scan_body_counted_once():
    w = jnp.ones((64, 64), jnp.float32)
    f = lambda x: jax.lax.scan(lambda c, _: (c @ w, None), x, None,
                               length=10)[0]
    ca = jax.jit(f).lower(jnp.ones((64, 64))).compile().cost_analysis()
    one = 2 * 64 ** 3
    assert ca["flops"] == pytest.approx(one, rel=0.01), \
        "premise broken: update §Roofline methodology"


def test_analytic_flops_vs_unrolled_compile():
    """Reduced qwen2 (4 layers), UNROLLED so XLA counts every layer; the
    analytic model must land within 25% (elementwise ops, norms and exact
    causal masking differ — matmuls dominate)."""
    from repro.analysis.flops import cell_cost
    from repro.configs import get_config, reduced
    from repro.configs.base import ShapeConfig
    from repro.models import RunConfig, loss_fn, init_params

    cfg = reduced(get_config("qwen2-7b"), layers=4, d_model=128,
                  n_heads=4, vocab=512).replace(tie_embeddings=False)
    rc = RunConfig(q_chunk=0, kv_chunk=64, loss_chunk=64, unroll=True)
    B, S = 4, 128
    params = jax.eval_shape(lambda k: init_params(cfg, k), jax.random.key(0))
    batch = {"tokens": jax.ShapeDtypeStruct((B, S), jnp.int32)}

    def step(p, b):
        return jax.grad(lambda p: loss_fn(p, cfg, rc, b)[0])(p)

    ca = jax.jit(step).lower(params, batch).compile().cost_analysis()
    shape = ShapeConfig("t", S, B, "train")
    cost = cell_cost(cfg, shape, chips=1, accum=1, remat=False)
    # analytic dispatch_flops excludes remat here; unrolled grad compile
    # does fwd+bwd (3x fwd matmuls)
    ratio = cost.dispatch_flops / ca["flops"]
    assert 0.75 < ratio < 1.33, (cost.dispatch_flops, ca["flops"], ratio)


def test_roofline_cell_analysis_shape():
    from repro.analysis.roofline import analyze_cell
    rec = {
        "arch": "qwen2-7b", "shape": "train_4k", "mesh": "16x16",
        "meta": {"accum": 4},
        "collectives": {"total_bytes": 500e9},
        "cost": {"flops": 1e12, "bytes accessed": 1e12},
        "memory": {"temp_bytes": 5e9, "argument_bytes": 2e9},
    }
    r = analyze_cell(rec)
    assert r.dominant == "collective"
    assert 0 < r.roofline_fraction() < 1
    assert r.fits_hbm is True
    assert 0 < r.flops_ratio <= 1
