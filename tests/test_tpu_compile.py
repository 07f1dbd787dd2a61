"""Compile the serving path's Pallas kernels for a TPU v5e, without one.

The other kernel tests run the kernels in interpret mode on the CPU,
which accepts block shapes and in-kernel ops that the TPU's Pallas
lowering refuses. Here each kernel is compiled by the TPU compiler for a
described (not attached) v5e chip at real widths: the paged-attention
superkernel at qwen1.5-0.5b's (GQA; bf16, int8 and fp8 pools; decode and
verify widths) and deepseek-v2's (MLA latents), the reduction engine's
row variant at the logit-stats shape and its flat variant at the paper's
dot sweep, and the whole qwen1.5-0.5b decode step (kernel dispatch
switched on) and prefill-chunk program, neither of which may copy a KV
pool into another layout. A compile proves lowering and fit; it runs
nothing.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and the worker given this file is
the one that does.
"""

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.ecm import hlo_cost
from repro.kernels import engine, ops
from repro.kernels import paged_attention as pa
from repro.models import api, attention, common
from repro.models.paged import PagedLayout
from repro.quant import core as qcore

V5E_HBM_BYTES = 16 * 2**30
# qwen1.5-0.5b serving geometry: 8 slots x 2048 context in 16-token blocks
SLOTS, MAX_CONTEXT, BLOCK = 8, 2048, 16


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler / library lock held elsewhere
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # compiles for a described chip are written to a persistent cache but
    # cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield jax.sharding.SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def _kernel_calls(compiled, name: str) -> int:
    """Number of ``tpu_custom_call``s whose call site is kernel ``name``."""
    return sum(1 for line in compiled.as_text().splitlines()
               if 'custom_call_target="tpu_custom_call"' in line
               and f"/{name}/" in line)


def _pool_relayouts(compiled, caches) -> list:
    """Copies of a pool layer or a stacked pool into a non-default
    layout: none, so no launch re-lays a stored pool out."""
    nb = caches[0]["kpool"].shape[1]
    return [c for c in hlo_cost.pool_copies(compiled.as_text(), nb)
            if not c.default]


# ---------------------------------------------------------- superkernel ----

@pytest.mark.parametrize("kv_dtype,width", [
    ("bf16", 1), ("bf16", 5), ("int8", 1), ("int8", 5), ("fp8", 1),
    ("fp8", 5)])
def test_superkernel_gqa_compiles(one_chip, kv_dtype, width):
    """qwen1.5-0.5b widths: 16 kv heads of 64, the 8-slot 2048-context
    pool; width 1 is decode, width 5 the spec-verify window (k = 4)."""
    cfg = get_config("qwen1.5-0.5b")
    hkv, d = cfg.num_kv_heads, cfg.head_dim
    layout = PagedLayout.for_context(MAX_CONTEXT, BLOCK)
    nb = 1 + SLOTS * layout.max_blocks
    fmt = qcore.get_format(kv_dtype)
    pool_dtype = jnp.bfloat16 if fmt is None else fmt.storage
    s = lambda shape, dt: _spec(one_chip, shape, dt)
    args = [s((SLOTS, width, cfg.num_heads, d), jnp.bfloat16),
            s((nb, BLOCK, hkv * d), pool_dtype),
            s((nb, BLOCK, hkv * d), pool_dtype),
            s((SLOTS, layout.max_blocks), jnp.int32),
            s((SLOTS,), jnp.int32), s((SLOTS,), jnp.int32)]
    if fmt is None:
        fn = pa.paged_attention_pallas
    else:
        args += [s((nb, BLOCK, hkv), jnp.float32)] * 2
        fn = lambda q, k, v, t, n, o, ks, vs: pa.paged_attention_pallas(
            q, k, v, t, n, o, kscale=ks, vscale=vs)
    assert _kernel_calls(_compile(fn, *args), "paged_attention") == 1


@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
@pytest.mark.parametrize("width", [1, 5])
def test_superkernel_mla_compiles(one_chip, kv_dtype, width):
    """deepseek-v2 latent widths: 128 heads over c_kv 512 + rope 64."""
    mla = get_config("deepseek-v2-236b").mla
    h, c, r = mla.num_heads, mla.kv_lora, mla.rope_dim
    nb, mb = 1 + SLOTS * 64, 64
    pool_dtype = jnp.bfloat16 if kv_dtype == "bf16" else jnp.int8
    s = lambda shape, dt: _spec(one_chip, shape, dt)
    args = [s((SLOTS, width, h, c), jnp.bfloat16),
            s((SLOTS, width, h, r), jnp.bfloat16),
            s((nb, BLOCK, c), pool_dtype), s((nb, BLOCK, r), pool_dtype),
            s((SLOTS, mb), jnp.int32), s((SLOTS,), jnp.int32),
            s((SLOTS,), jnp.int32)]
    scale = (mla.nope_dim + r) ** -0.5
    if kv_dtype == "bf16":
        fn = lambda *a: pa.paged_latent_attention_pallas(*a, scale=scale)
    else:
        args += [s((nb, BLOCK), jnp.float32)] * 2
        fn = lambda *a: pa.paged_latent_attention_pallas(
            *a[:7], ck_scale=a[7], kr_scale=a[8], scale=scale)
    assert _kernel_calls(_compile(fn, *args), "paged_attention") == 1


# ---------------------------------------------------- reduction engine ----

@pytest.mark.parametrize("outputs", [("max", "sum", "sumsq"), ("sum",)])
def test_fused_reduce_rows_compiles(one_chip, outputs):
    """The two engine passes of ``_logit_stats`` at [8 slots, vocab]."""
    x = _spec(one_chip, (SLOTS, get_config("qwen1.5-0.5b").vocab_size),
              jnp.float32)
    fn = lambda x: engine.fused_reduce_rows((x,), outputs=outputs)
    assert _kernel_calls(_compile(fn, x), "fused_reduce_rows") == 1


@pytest.mark.parametrize("unroll", [1, 2, 4, 8])
def test_fused_reduce_flat_dot_compiles(one_chip, unroll):
    """The paper's compensated dot at 2^20 elements, across the U sweep."""
    x = _spec(one_chip, (2**20,), jnp.float32)
    fn = lambda x, y: engine.fused_reduce_flat((x, y), outputs=("dot",),
                                               unroll=unroll)
    assert _kernel_calls(_compile(fn, x, x), "fused_reduce_flat") == 1


# ------------------------------------------------------------ decode step --

def test_decode_step_compiles_with_kernels(one_chip, monkeypatch):
    """The engine's whole qwen1.5-0.5b decode launch (24 layers, vocab
    151936, the logit-stats pass and the merge that holds non-decoding
    rows), with the TPU dispatch switched on: the superkernel and the
    reduction engine lower inside it, no pool is re-laid out, and the
    program fits one chip's HBM."""
    from repro.serving.engine import fused_decode_fn

    monkeypatch.setattr(attention, "paged_kernel_enabled", lambda: True)
    monkeypatch.setattr(ops, "_auto_interpret",
                        lambda interpret: bool(interpret))
    cfg = get_config("qwen1.5-0.5b")
    layout = PagedLayout.for_context(MAX_CONTEXT, BLOCK)
    place = lambda t: jax.tree.map(
        lambda x: _spec(one_chip, x.shape, x.dtype), t)
    params = place(common.abstract_params(api.schema(cfg)))
    caches = place(api.cache_specs(cfg, SLOTS, layout))
    feed = (_spec(one_chip, (SLOTS, 1), jnp.int32),
            _spec(one_chip, (SLOTS,), jnp.bool_))
    compiled = _compile(fused_decode_fn(cfg), params, feed, caches)
    assert _kernel_calls(compiled, "paged_attention") >= 1
    assert _kernel_calls(compiled, "fused_reduce_rows") == 2
    assert _pool_relayouts(compiled, caches) == []
    mem = compiled.memory_analysis()
    need = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert need < V5E_HBM_BYTES, need


def test_prefill_chunk_compiles_without_pool_relayout(one_chip):
    """The whole qwen1.5-0.5b prefill-chunk program (32 tokens of one
    slot into the 8-slot pools): it fits one chip, and no copy re-lays a
    pool layer or the stacked pools out."""
    cfg = get_config("qwen1.5-0.5b")
    layout = PagedLayout.for_context(MAX_CONTEXT, BLOCK)
    place = lambda t: jax.tree.map(
        lambda x: _spec(one_chip, x.shape, x.dtype), t)
    params = place(common.abstract_params(api.schema(cfg)))
    caches = place(api.cache_specs(cfg, SLOTS, layout))
    scalar = _spec(one_chip, (), jnp.int32)
    compiled = _compile(api.prefill_chunk_fn(cfg), params,
                        _spec(one_chip, (1, 32), jnp.int32), caches,
                        scalar, scalar)
    assert _pool_relayouts(compiled, caches) == []
    mem = compiled.memory_analysis()
    need = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert need < V5E_HBM_BYTES, need
