"""Validate the trip-count-aware HLO cost model against known workloads."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.ecm import hlo_cost


def _compile(f, *args, in_shardings=None):
    if in_shardings is not None:
        jitted = jax.jit(f, in_shardings=in_shardings)
    else:
        jitted = jax.jit(f)
    return jitted.lower(*args).compile()


def test_scan_matmul_flops_trip_count():
    """12-layer scan of 256x256x256 matmuls: exactly 12 x 2 x 256^3 dot
    flops (XLA's own cost_analysis reports 1/12th of this)."""
    def f(x, ws):
        def body(c, w):
            return c @ w, None
        y, _ = jax.lax.scan(body, x, ws)
        return y

    x = jax.ShapeDtypeStruct((256, 256), jnp.float32)
    ws = jax.ShapeDtypeStruct((12, 256, 256), jnp.float32)
    compiled = _compile(f, x, ws)
    got = hlo_cost.analyze(compiled.as_text())
    expect = 12 * 2 * 256 ** 3
    assert got.dot_flops == pytest.approx(expect, rel=0.01), got.dot_flops
    # XLA undercounts by the trip count — this is the bug we fix
    xla = compiled.cost_analysis()
    if isinstance(xla, (list, tuple)):
        xla = xla[0]
    assert float(xla["flops"]) < expect / 2


def test_plain_matmul_flops():
    def f(a, b):
        return a @ b
    a = jax.ShapeDtypeStruct((128, 512), jnp.float32)
    b = jax.ShapeDtypeStruct((512, 64), jnp.float32)
    compiled = _compile(f, a, b)
    got = hlo_cost.analyze(compiled.as_text())
    assert got.dot_flops == pytest.approx(2 * 128 * 512 * 64, rel=0.01)


def test_nested_scan_multiplies():
    def f(x, ws):
        def outer(c, w):
            def inner(ci, _):
                return ci @ w, None
            ci, _ = jax.lax.scan(inner, c, None, length=5)
            return ci, None
        y, _ = jax.lax.scan(outer, x, ws)
        return y

    x = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    ws = jax.ShapeDtypeStruct((3, 64, 64), jnp.float32)
    compiled = _compile(f, x, ws)
    got = hlo_cost.analyze(compiled.as_text())
    assert got.dot_flops == pytest.approx(3 * 5 * 2 * 64 ** 3, rel=0.01)


def test_bytes_scale_with_trip_count():
    def f(x, ws):
        def body(c, w):
            return c + w, None
        y, _ = jax.lax.scan(body, x, ws)
        return y
    x = jax.ShapeDtypeStruct((1024,), jnp.float32)
    ws = jax.ShapeDtypeStruct((10, 1024), jnp.float32)
    compiled = _compile(f, x, ws)
    got = hlo_cost.analyze(compiled.as_text())
    # each step reads >= 2x4KB and writes >= 4KB, 10 times
    assert got.bytes_accessed >= 10 * 3 * 4096
    assert got.elementwise_flops >= 10 * 1024


def _cost_of(f, *args) -> "hlo_cost.HloCost":
    return hlo_cost.analyze(jax.jit(f).lower(*args).compile().as_text())


def test_fused_decode_launch_layers_linear():
    """The engine's fused decode launch (model step + argmax + logit
    stats, the profiler's ``decode_step`` phase) must cost linearly in
    ``num_layers``: the transformer stack is a scan, so an analyzer that
    ignores trip counts under-reports by ~L× — exactly the class of bug
    the attribution profiler cannot tolerate (it would misprice every
    decode row). Per-layer increments across L=2,4,6 must agree."""
    from repro.configs import get_config, reduced
    from repro.models import api, common

    costs = {}
    for layers in (2, 4, 6):
        cfg = reduced(get_config("qwen1.5-0.5b")).with_(num_layers=layers)
        params = common.abstract_params(api.schema(cfg))
        kv = api.KVCache.build(cfg, max_context=64, block_size=16,
                               max_slots=2)
        tokens = jax.ShapeDtypeStruct((2, 1), jnp.int32)
        costs[layers] = _cost_of(api.decode_fn(cfg), params, tokens,
                                 kv.specs(2))
    for field in ("dot_flops", "bytes_accessed"):
        d1 = getattr(costs[4], field) - getattr(costs[2], field)
        d2 = getattr(costs[6], field) - getattr(costs[4], field)
        assert d1 > 0, (field, costs)
        assert d2 == pytest.approx(d1, rel=0.05), \
            f"{field}: per-layer increment not constant " \
            f"({d1:g} vs {d2:g}) — scan trip count dropped?"
    # the increment is a whole transformer layer, not rounding noise:
    # >= the layer's four attention projections alone (d_model^2 matmuls)
    cfg2 = reduced(get_config("qwen1.5-0.5b"))
    floor = 2 * 4 * 2 * cfg2.d_model ** 2      # B=2 rows, 4 proj, 2NK flops
    assert costs[4].dot_flops - costs[2].dot_flops >= 2 * floor


def test_paged_attention_superkernel_blocks_linear():
    """The paged-attention superkernel walks one pool block per grid
    step over the table's static width ``mb`` — flops and bytes must
    scale linearly in the block count at fixed pool size. Catches a
    cost model that prices only one grid step (or the whole pool) for
    the profiler's dominant HBM term."""
    from repro.kernels import ops

    bs, hkv, hq, d, b = 16, 2, 4, 32, 2
    kpool = jax.ShapeDtypeStruct((9, bs, hkv * d), jnp.float32)
    vpool = jax.ShapeDtypeStruct((9, bs, hkv * d), jnp.float32)
    q = jax.ShapeDtypeStruct((b, 1, hq, d), jnp.float32)
    lens = jax.ShapeDtypeStruct((b,), jnp.int32)

    def attn(q, kpool, vpool, table, lens):
        return ops.paged_attention(q, kpool, vpool, table, lens,
                                   interpret=True)

    costs = {}
    for mb in (2, 4, 8):
        table = jax.ShapeDtypeStruct((b, mb), jnp.int32)
        costs[mb] = _cost_of(attn, q, kpool, vpool, table, lens)
    for field in ("dot_flops", "bytes_accessed"):
        d1 = getattr(costs[4], field) - getattr(costs[2], field)
        d2 = getattr(costs[8], field) - getattr(costs[4], field)
        assert d1 > 0, (field, {k: getattr(v, field)
                                for k, v in costs.items()})
        assert d2 == pytest.approx(2 * d1, rel=0.10), \
            f"{field}: block increments not linear ({d1:g}, {d2:g})"
    # per-block dot work floor: the score matmul alone is
    # 2 * rows * bs * d flops per (batch, kv-head) pair of a grid step
    rows = 32                                   # _ROW_TILE padding
    per_block_floor = b * hkv * 2 * rows * bs * d
    assert (costs[4].dot_flops - costs[2].dot_flops) >= 2 * per_block_floor


@pytest.mark.skipif(jax.device_count() != 8,
                    reason="needs xla_force_host_platform_device_count=8")
def test_collectives_in_scan_counted_with_trips():
    from jax.sharding import NamedSharding, PartitionSpec as P
    mesh = jax.make_mesh((2, 4), ("data", "model"))

    def f(x, ws):
        def body(c, w):
            return jax.nn.relu(c @ w), None
        y, _ = jax.lax.scan(body, x, ws)
        return y

    x = jax.ShapeDtypeStruct((256, 256), jnp.float32)
    ws = jax.ShapeDtypeStruct((12, 256, 256), jnp.float32)
    shx = NamedSharding(mesh, P("data", "model"))
    shw = NamedSharding(mesh, P(None, "data", "model"))
    compiled = _compile(f, x, ws, in_shardings=(shx, shw))
    got = hlo_cost.analyze(compiled.as_text())
    total_count = sum(got.collective_count.values())
    assert total_count >= 12          # at least one collective per layer
    assert got.weighted_collective_bytes > 0
