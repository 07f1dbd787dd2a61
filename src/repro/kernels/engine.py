"""Streaming multi-stream reduction engine — one Pallas kernel family.

This module is the single implementation behind every reduction kernel in
the repo (``kahan_dot``, ``kahan_sum``, ``naive_dot``, the fused
multi-reductions and the batched row-dot). It implements the paper's two
performance prerequisites for "Kahan for free" (Hofmann et al.,
arXiv:1604.01890 §4.2) on the TPU VPU:

1. **SIMD vectorization** — every accumulator is a full ``(8, 128)`` vreg:
   one compensated ``(sum, carry)`` pair per (sublane, lane).

2. **Mod-U unrolling** — the un-unrolled compensated loop is *latency*
   bound: each Neumaier step is ~7 VPU ops of which ~5 sit on a serial
   dependency chain, so folding every ``(8, 128)`` chunk into a single
   persistent accumulator serializes the whole stream on ADD latency
   (the paper measures this as a multi-x in-cache slowdown). The engine
   instead reshapes each VMEM block to ``(U, chunks, 8, 128)`` and keeps
   ``U`` independent accumulator *streams*; one vectorized Neumaier step
   updates all ``U`` streams at once, cutting the dependency chain by U
   and letting Mosaic overlap the independent updates. ``U`` is a static
   tuned parameter (swept in ``benchmarks/bench_kernel_throughput.py``;
   defaults from ``DEFAULT_UNROLL``).

3. **Compensated merge at loop exit** — the U streams, then sublanes,
   then lanes are merged pairwise with TwoSum (``kahan.combine``), the
   paper's "reduce partial sums scalar-ly at the end" strategy, so the
   final fold reintroduces no O(streams·eps) error.

Operands are laid out as lane-dense ``(rows, 128)`` tiles and streamed
in ``(block_rows, 128)`` blocks, ``block_rows`` a multiple of ``8 * U``:
the layout the TPU's Pallas lowering accepts for any operand length. An
operand whose length is a multiple of 128 (the hot paths: vocab rows,
power-of-two sweeps) is re-viewed for free; a ragged one pays a single
pad to the next lane multiple. The final partial block is masked
in-kernel against the static element count (global-iota compare), so
the kernel is independent of the pad values and of the unspecified tail
Pallas loads past the array end.

Fused multi-reduction: one pass over the operands can emit any subset of

  ``dot``     Σ x·y        (compensated; requires two operands)
  ``sum``     Σ x          (compensated)
  ``sumsq``   Σ x²         (compensated; nrm2 = sqrt(sumsq))
  ``max``     max x        (plain running max)
  ``maxabs``  max |x|      (plain running max)

in a single ``pallas_call`` — HBM traffic is paid once instead of once
per statistic. The batched-rows variant runs many independent reductions
(one per row) in one launch, sequentially along the inner grid axis.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import kahan

SUBLANES = 8
LANES = 128
TILE = SUBLANES * LANES          # one (8, 128) vreg = 1024 elements

# Outputs that maintain a compensated (sum, carry) accumulator pair.
COMPENSATED_OUTPUTS = ("dot", "sum", "sumsq")
# Outputs that maintain a plain running-max accumulator.
MAX_OUTPUTS = ("max", "maxabs")
ALL_OUTPUTS = COMPENSATED_OUTPUTS + MAX_OUTPUTS

# Small autotune table: default unroll per fused-output family, from the
# U-sweep in benchmarks/bench_kernel_throughput.py (v5e VPU: U=4 already
# hides the ~5-op Neumaier dependency chain; U=8 buys nothing more but
# doubles scratch). Keyed by the primary compensated output.
DEFAULT_UNROLL = {"dot": 4, "sum": 4, "sumsq": 4, None: 4}

DEFAULT_BLOCK_ELEMS = 32 * TILE  # 32768 elements = 128 KiB f32 per operand


def default_unroll(outputs) -> int:
    for o in outputs:
        if o in COMPENSATED_OUTPUTS:
            return DEFAULT_UNROLL[o]
    return DEFAULT_UNROLL[None]


def _check_outputs(outputs, n_operands: int) -> tuple[str, ...]:
    outputs = tuple(outputs)
    assert outputs, "need at least one output"
    for o in outputs:
        assert o in ALL_OUTPUTS, o
    if "dot" in outputs:
        assert n_operands == 2, "'dot' needs two operands"
    return outputs


def pick_block_elems(n: int, unroll: int,
                     requested: int = DEFAULT_BLOCK_ELEMS) -> int:
    """Largest block <= ~requested that keeps a non-trivial grid for small
    inputs; always an exact multiple of unroll * TILE (the engine's stream
    granule), whatever ``requested`` is."""
    floor = unroll * TILE
    k = max(requested // floor, 1)       # block size in stream granules
    while k > 1 and k * floor >= 2 * max(n, 1):
        k //= 2
    return k * floor


# --------------------------------------------------------------- folds ----

def _binary_fold_axis(s, c, axis: int):
    """Halve ``axis`` repeatedly, merging (sum, carry) pairs with TwoSum."""
    size = s.shape[axis]
    while size > 1:
        half = size // 2
        lo = lambda a: jax.lax.slice_in_dim(a, 0, half, axis=axis)
        hi = lambda a: jax.lax.slice_in_dim(a, half, size, axis=axis)
        s, c = kahan.combine(lo(s), lo(c), hi(s), hi(c))
        size = half
    return s, c


def _fold_streams(s, c):
    """(U, 8, 128) compensated accumulators -> () scalar pair.

    Streams, then sublanes, then lanes: log2(U) + 3 + 7 compensated merge
    levels, each a TwoSum (no compensation lost at the fold).
    """
    for axis in (0, 1, 2):
        s, c = _binary_fold_axis(s, c, axis)
    return s.reshape(()), c.reshape(())


# -------------------------------------------------------------- kernel ----

def _engine_kernel(*refs, outputs, n_operands, n_valid, block_elems,
                   unroll, acc_dtype, compensated, batched):
    """Grid-sequential fused reduction body.

    ``refs`` layout: operand refs ((block_rows, 128) tiles, with a
    leading unit row axis when ``batched``), then one (8, 128) out ref
    per output (the statistic broadcast over the tile), then scratch
    refs (a (U,8,128) sum + carry pair per compensated output — or a
    single (8,128) plain accumulator in naive mode — and one (8,128)
    running-max buffer per max output).
    """
    operands = refs[:n_operands]
    out_refs = refs[n_operands:n_operands + len(outputs)]
    scratch = list(refs[n_operands + len(outputs):])

    j = pl.program_id(1) if batched else pl.program_id(0)
    nj = pl.num_programs(1) if batched else pl.num_programs(0)

    comp_accs, max_accs = {}, {}
    for o in outputs:      # same order as _scratch_shapes
        if o in COMPENSATED_OUTPUTS:
            if compensated:
                comp_accs[o] = (scratch.pop(0), scratch.pop(0))
            else:
                comp_accs[o] = (scratch.pop(0), None)
        else:
            max_accs[o] = scratch.pop(0)

    @pl.when(j == 0)
    def _init():
        for s_ref, c_ref in comp_accs.values():
            s_ref[...] = jnp.zeros_like(s_ref)
            if c_ref is not None:
                c_ref[...] = jnp.zeros_like(c_ref)
        for o, m_ref in max_accs.items():
            fill = 0.0 if o == "maxabs" else -jnp.inf
            m_ref[...] = jnp.full_like(m_ref, fill)

    rows = block_elems // LANES
    # Global element index of each lane of this block; the final partial
    # block is masked against the static element count so the engine never
    # needs host-side zero padding (Pallas leaves the out-of-bounds tail
    # of the last block unspecified).
    base = j * block_elems
    idx = (base
           + jax.lax.broadcasted_iota(jnp.int32, (rows, LANES), 0) * LANES
           + jax.lax.broadcasted_iota(jnp.int32, (rows, LANES), 1))
    valid = idx < n_valid

    loaded = []
    for ref in operands:
        v = ref[...].reshape(rows, LANES).astype(acc_dtype)
        loaded.append(jnp.where(valid, v, jnp.zeros_like(v)))
    x = loaded[0]
    y = loaded[1] if n_operands == 2 else None

    contribs = {}
    for o in outputs:
        if o == "dot":
            contribs[o] = x * y      # exact in f32 for bf16 inputs
        elif o == "sum":
            contribs[o] = x
        elif o == "sumsq":
            contribs[o] = x * x

    chunks = block_elems // (unroll * TILE)

    for o, (s_ref, c_ref) in comp_accs.items():
        if not compensated:
            # Paper baseline: plain per-vreg partial sums, no carry.
            partial = contribs[o].reshape(-1, SUBLANES, LANES).sum(axis=0)
            s_ref[...] = s_ref[...] + partial
            continue
        # Mod-U unroll: U independent streams, each fed a contiguous
        # segment of the block. One vectorized Neumaier step updates all
        # U (8,128) accumulators at once; the serial dependency chain per
        # block is `chunks` steps instead of `chunks * U`.
        # The chunk loop is unrolled at trace time (static indices only:
        # the TPU lowering has no in-kernel dynamic_slice of a value).
        r = contribs[o].reshape(unroll, chunks, SUBLANES, LANES)
        s, c = s_ref[...], c_ref[...]
        for i in range(chunks):
            s, c = kahan.neumaier_step(s, c, r[:, i])
        s_ref[...] = s
        c_ref[...] = c

    for o, m_ref in max_accs.items():
        v = jnp.abs(x) if o == "maxabs" else jnp.where(valid, x, -jnp.inf)
        partial = v.reshape(-1, SUBLANES, LANES).max(axis=0)
        m_ref[...] = jnp.maximum(m_ref[...], partial)

    @pl.when(j == nj - 1)
    def _finish():
        for o, out_ref in zip(outputs, out_refs):
            if o in COMPENSATED_OUTPUTS:
                s_ref, c_ref = comp_accs[o]
                if compensated:
                    fs, fc = _fold_streams(s_ref[...], c_ref[...])
                    val = fs + fc
                else:
                    val = jnp.sum(s_ref[...])
            else:
                val = jnp.max(max_accs[o][...])
            out_ref[...] = jnp.broadcast_to(val, out_ref.shape).astype(
                out_ref.dtype)


# ----------------------------------------------------------- launchers ----

def _scratch_shapes(outputs, unroll, acc_dtype, compensated):
    shapes = []
    for o in outputs:
        if o in COMPENSATED_OUTPUTS:
            if compensated:
                shapes.append(pltpu.VMEM((unroll, SUBLANES, LANES), acc_dtype))
                shapes.append(pltpu.VMEM((unroll, SUBLANES, LANES), acc_dtype))
            else:
                shapes.append(pltpu.VMEM((SUBLANES, LANES), acc_dtype))
        else:
            shapes.append(pltpu.VMEM((SUBLANES, LANES), acc_dtype))
    return shapes


def _lane_tiles(x: jax.Array) -> jax.Array:
    """[..., n] -> [..., ceil(n / 128), 128]: free for n % 128 == 0,
    otherwise one pad of the last axis to the next lane multiple (the
    kernel masks the pad)."""
    pad = -x.shape[-1] % LANES
    if pad:
        x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])
    return x.reshape(*x.shape[:-1], x.shape[-1] // LANES, LANES)


def fused_reduce_flat(operands, *, outputs, unroll: int | None = None,
                      block_elems: int | None = None,
                      compensated: bool = True,
                      interpret: bool = False):
    """Fused reduction of flat 1-D operands -> tuple of () scalars.

    All requested ``outputs`` are produced in ONE streaming pass (one
    ``pallas_call``): the operands cross HBM once regardless of how many
    statistics are emitted.
    """
    operands = tuple(operands)
    outputs = _check_outputs(outputs, len(operands))
    n = operands[0].shape[0]
    for op in operands:
        assert op.ndim == 1 and op.shape[0] == n, op.shape
    assert n >= 1, "empty reduction"
    unroll = default_unroll(outputs) if unroll is None else unroll
    assert unroll >= 1 and (unroll & (unroll - 1)) == 0, unroll
    block_elems = (pick_block_elems(n, unroll) if block_elems is None
                   else block_elems)
    assert block_elems % (unroll * TILE) == 0, (block_elems, unroll)
    acc_dtype = jnp.promote_types(operands[0].dtype, jnp.float32)
    block_rows = block_elems // LANES
    grid = (pl.cdiv(n, block_elems),)

    outs = pl.pallas_call(
        functools.partial(
            _engine_kernel, outputs=outputs, n_operands=len(operands),
            n_valid=n, block_elems=block_elems, unroll=unroll,
            acc_dtype=acc_dtype, compensated=compensated, batched=False),
        grid=grid,
        in_specs=[pl.BlockSpec((block_rows, LANES), lambda g: (g, 0))
                  for _ in operands],
        out_specs=[pl.BlockSpec((SUBLANES, LANES), lambda g: (0, 0))
                   for _ in outputs],
        out_shape=[jax.ShapeDtypeStruct((SUBLANES, LANES), acc_dtype)
                   for _ in outputs],
        scratch_shapes=_scratch_shapes(outputs, unroll, acc_dtype,
                                       compensated),
        interpret=interpret,
        name="fused_reduce_flat",
    )(*(_lane_tiles(op) for op in operands))
    return tuple(o[0, 0] for o in outs)


def fused_reduce_rows(operands, *, outputs, unroll: int | None = None,
                      block_elems: int | None = None,
                      compensated: bool = True,
                      interpret: bool = False):
    """Batched row reduction: (B, N) operands -> tuple of (B,) arrays.

    Many independent reductions per launch (grid = (B, blocks-per-row));
    the inner grid axis streams one row's blocks through the same
    accumulator scratch, the outer axis advances to the next row. This is
    the serving-engine logprob/metric path: all rows' statistics in one
    kernel instead of one pass per statistic.
    """
    operands = tuple(operands)
    outputs = _check_outputs(outputs, len(operands))
    b, n = operands[0].shape
    for op in operands:
        assert op.shape == (b, n), (op.shape, (b, n))
    assert n >= 1
    unroll = default_unroll(outputs) if unroll is None else unroll
    assert unroll >= 1 and (unroll & (unroll - 1)) == 0, unroll
    block_elems = (pick_block_elems(n, unroll) if block_elems is None
                   else block_elems)
    assert block_elems % (unroll * TILE) == 0, (block_elems, unroll)
    acc_dtype = jnp.promote_types(operands[0].dtype, jnp.float32)
    block_rows = block_elems // LANES
    grid = (b, pl.cdiv(n, block_elems))

    outs = pl.pallas_call(
        functools.partial(
            _engine_kernel, outputs=outputs, n_operands=len(operands),
            n_valid=n, block_elems=block_elems, unroll=unroll,
            acc_dtype=acc_dtype, compensated=compensated, batched=True),
        grid=grid,
        in_specs=[pl.BlockSpec((1, block_rows, LANES),
                               lambda i, g: (i, g, 0))
                  for _ in operands],
        out_specs=[pl.BlockSpec((1, SUBLANES, LANES),
                                lambda i, g: (i, 0, 0))
                   for _ in outputs],
        out_shape=[jax.ShapeDtypeStruct((b, SUBLANES, LANES), acc_dtype)
                   for _ in outputs],
        scratch_shapes=_scratch_shapes(outputs, unroll, acc_dtype,
                                       compensated),
        interpret=interpret,
        name="fused_reduce_rows",
    )(*(_lane_tiles(op) for op in operands))
    return tuple(o[:, 0, 0] for o in outs)
