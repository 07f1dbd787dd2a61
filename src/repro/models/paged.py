"""Block-paged KV cache layout: block pool + per-sequence block tables.

The serving layer's KV memory discipline follows the paper's rule — pay
memory traffic for what a request actually uses, not for the worst case.
A contiguous per-slot cache row reserves (and, on every decode step,
touches) ``max_context`` tokens per slot regardless of the sequence's real
length. The paged layout instead carves the cache into fixed-size token
*blocks* drawn from one shared pool:

  pool         [num_blocks, block_size, ...]   KV data, shared by all slots
  block_table  [B, max_blocks] int32           per-slot pool-block indices
  len          [B] int32                       valid tokens per slot

The pool's trailing dims are the cache module's: GQA K/V pools are
[nb, bs, hkv * d] (lane-dense, the layout the paged-attention kernel
reads), their quantized scale tiles [nb, bs, hkv], MLA latent pools
[nb, bs, C]. Everything here addresses the block and position axes and
passes the trailing dims through.

Block 0 is the reserved **null block**: it is never allocated, inactive
slots' tables point at it, and any stray write (a masked-out slot in the
batched decode step) lands there harmlessly. A slot therefore only ever
touches ``ceil(len / block_size)`` blocks — the KV-bytes-touched win
measured in ``benchmarks/bench_serving.py``.

The transforms here are pure layout moves (reshape / gather / scatter):
``gather_blocks(pool_from_rows(rows), identity_table(...))`` returns the
padded rows bit-for-bit, which is what makes the paged decode path match
the contiguous formulation bitwise (tests/test_paged_kv.py).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array

DEFAULT_BLOCK_SIZE = 16
NULL_BLOCK = 0          # reserved pool block; never allocated to a slot


def cdiv(a: int, b: int) -> int:
    return -(-a // b)


class PagedLayout(NamedTuple):
    """Per-sequence paging geometry (pool sizing is the allocator's call)."""

    block_size: int      # tokens per KV block
    max_blocks: int      # block-table length per sequence

    @property
    def max_context(self) -> int:
        return self.block_size * self.max_blocks

    def blocks_for(self, num_tokens: int) -> int:
        """Pool blocks a sequence of ``num_tokens`` occupies (the single
        source for admission gating and pool sizing)."""
        return min(cdiv(num_tokens, self.block_size), self.max_blocks)

    @staticmethod
    def for_context(max_context: int,
                    block_size: int = DEFAULT_BLOCK_SIZE) -> "PagedLayout":
        return PagedLayout(block_size, cdiv(max_context, block_size))


def as_layout(spec) -> PagedLayout:
    """Accept an int max-context (legacy ``cache_size``) or a PagedLayout."""
    if isinstance(spec, PagedLayout):
        return spec
    return PagedLayout.for_context(int(spec))


def default_num_blocks(layout: PagedLayout, batch: int) -> int:
    """Pool size that can hold ``batch`` full-context sequences + null."""
    return 1 + batch * layout.max_blocks


def padded_num_blocks(layout: PagedLayout, batch: int, multiple: int) -> int:
    """``default_num_blocks`` rounded up so the pool's block axis divides
    ``multiple`` — lets the dry-run shard the pool over the data axes
    (distributed serving keeps per-chip KV at pool/data bytes)."""
    return cdiv(default_num_blocks(layout, batch), multiple) * multiple


def identity_table(batch: int, layout: PagedLayout) -> Array:
    """Dense block table: slot b owns blocks [1 + b*mb, 1 + (b+1)*mb)."""
    mb = layout.max_blocks
    return (1 + jnp.arange(batch, dtype=jnp.int32)[:, None] * mb
            + jnp.arange(mb, dtype=jnp.int32)[None, :])


def pool_from_rows(rows: Array, layout: PagedLayout) -> Array:
    """[B, S, ...] contiguous rows -> [1 + B*mb, bs, ...] pool whose
    identity-table gather reproduces the (padded) rows bitwise."""
    b, s = rows.shape[:2]
    bs, mb = layout.block_size, layout.max_blocks
    assert s <= layout.max_context, (s, layout)
    pad = mb * bs - s
    if pad:
        rows = jnp.pad(rows, [(0, 0), (0, pad)] + [(0, 0)] * (rows.ndim - 2))
    blocks = rows.reshape((b * mb, bs) + rows.shape[2:])
    null = jnp.zeros((1,) + blocks.shape[1:], blocks.dtype)
    return jnp.concatenate([null, blocks], axis=0)


def gather_blocks(pool: Array, table: Array) -> Array:
    """[nb, bs, ...] pool + [B, mb] table -> [B, mb*bs, ...] virtual rows."""
    b, mb = table.shape
    bs = pool.shape[1]
    gathered = jnp.take(pool, table.reshape(-1), axis=0)
    return gathered.reshape((b, mb * bs) + pool.shape[2:])


def scatter_token(pool: Array, table: Array, lens: Array, vals: Array
                  ) -> Array:
    """Write one token per sequence at its current length.

    pool [nb, bs, ...]; table [B, mb]; lens [B]; vals [B, ...]. Out-of-range
    positions (an idle slot whose length a batched step lets drift, as the
    draft model's does between drafts) clip into the table row, whose stale
    entries are the null block — the write is absorbed there.
    """
    bs, mb = pool.shape[1], table.shape[1]
    blk_idx = jnp.clip(lens // bs, 0, mb - 1)
    blk = jnp.take_along_axis(table, blk_idx[:, None], axis=1)[:, 0]
    off = lens % bs
    return pool.at[blk, off].set(vals)


def scatter_chunk(pool: Array, table_row: Array, pos0, vals: Array) -> Array:
    """Write a C-token chunk of ONE sequence at positions pos0..pos0+C-1.

    pool [nb, bs, ...]; table_row [mb]; vals [C, ...]; pos0 dynamic scalar.
    """
    c = vals.shape[0]
    bs, mb = pool.shape[1], table_row.shape[0]
    pos = pos0 + jnp.arange(c, dtype=jnp.int32)
    blk = jnp.take(table_row, jnp.clip(pos // bs, 0, mb - 1))
    return pool.at[blk, pos % bs].set(vals)


def scatter_chunk_multi(pool: Array, tables: Array, pos0s: Array,
                        vals: Array) -> Array:
    """Write a C-token chunk for EACH of S sequences in one scatter.

    pool [nb, bs, ...]; tables [S, mb]; pos0s [S]; vals [S, C, ...]. The
    speculative verify pass appends every slot's draft window in one launch.
    Slots never share pool blocks, so cross-slot writes cannot collide; a
    duplicated (slot, pos0, vals) row — the fixed-shape padding the spec
    engine uses — writes identical values twice, which ``.at[].set`` resolves
    deterministically. Positions past the table's span are routed to the
    null block EXPLICITLY: when a slot owns every table entry (prompt +
    max_new == max_context) there is no null tail to clip into, and a
    clipped write would corrupt the slot's own cached history.
    """
    s, c = vals.shape[:2]
    bs, mb = pool.shape[1], tables.shape[1]
    pos = pos0s[:, None] + jnp.arange(c, dtype=jnp.int32)[None, :]   # [S, C]
    blk_idx = pos // bs
    blk = jnp.take_along_axis(tables, jnp.clip(blk_idx, 0, mb - 1), axis=1)
    blk = jnp.where(blk_idx < mb, blk, NULL_BLOCK)
    return pool.at[blk, pos % bs].set(vals)


# ------------------------------------------------------ cache-tree surgery --

# Leaf names that are shared block pools (no batch axis — never reset
# per-slot; stale data in re-allocated blocks is masked by ``len``).
# Quantized pools (repro.quant) carry per-block scale tiles addressed
# through the same block table — they are pools too: reset_slot must not
# batch-index them and serve_cache_shardings must never split their
# block-internal position axis.
POOL_KEYS = ("kpool", "vpool", "c_kv", "k_rope",
             "kscale", "vscale", "c_kv_scale", "k_rope_scale")


def keep_slots(old, new, keep_mask: Array):
    """Merge two batched LM cache trees after a full-batch step: slots
    flagged in ``keep_mask`` ([B] bool) keep their OLD per-slot state.

    The batched decode step updates every slot — including free ones and
    ones that are mid-chunked-prefill. Attention slots tolerate the stray
    token write (it is positional, and the next chunk overwrites it or the
    null block absorbs it), but recurrent per-slot state (SSM state/conv
    window, ``len``) would be polluted for good, and a free slot's ``len``
    would grow every step. Shared pool leaves pass through from ``new``
    (their stray writes land inside the protected slot's own blocks at
    positions the next chunk rewrites, or in the null block).
    """
    from jax.tree_util import tree_map_with_path

    def one(path, o, n):
        name = str(getattr(path[-1], "key", path[-1]))
        if name in POOL_KEYS:
            return n
        keep = keep_mask.reshape((1, -1) + (1,) * (o.ndim - 2))
        return jnp.where(keep, o, n)

    return tree_map_with_path(one, old, new)


def set_lens(caches, slots: Array, new_lens: Array):
    """Set per-slot cached lengths of a batched LM cache tree: ``len``
    leaves ([L, B]) get ``len[:, slots] = new_lens``; everything else passes
    through untouched.

    This is the speculative-decode rollback: a rejected draft suffix is
    undone purely by decrementing the slot's length — the pool blocks stay
    allocated and the stale rows beyond ``len`` are masked by every reader
    and overwritten by the next append. Duplicate ``slots`` entries (the
    spec engine's fixed-shape padding) must carry identical values.
    """
    from jax.tree_util import tree_map_with_path

    def one(path, leaf):
        name = str(getattr(path[-1], "key", path[-1]))
        if name == "len":
            return leaf.at[:, slots].set(new_lens[None, :])
        return leaf

    return tree_map_with_path(one, caches)


def copy_block(caches, src, dst):
    """Copy ONE pool block ``src`` -> ``dst`` across every pool leaf and
    layer of a batched LM cache tree (scale tiles included — they ride
    the same block ids).

    This is the copy-on-write step of prefix caching: a request whose
    prompt diverges mid-block from a cached prefix gets a private copy of
    the divergence block, and only the copy enters its block table — the
    shared original stays bit-identical for every other reader. Pool
    leaves are [L, num_blocks, block_size, ...]; everything else (tables,
    lens, recurrent state) passes through untouched.
    """
    from jax.tree_util import tree_map_with_path

    def one(path, leaf):
        name = str(getattr(path[-1], "key", path[-1]))
        if name in POOL_KEYS:
            return leaf.at[:, dst].set(leaf[:, src])
        return leaf

    return tree_map_with_path(one, caches)


def extract_blocks(caches, blocks) -> dict:
    """Gather the listed pool blocks out of every pool leaf of a batched
    LM cache tree: ``{leaf path: array [L, len(blocks), block_size, ...]}``.

    This is the device half of preemption-to-host
    (``repro.serving.swap.KVSwap``): the snapshot covers EVERY pool leaf
    — quantized payloads and their per-block scale tiles alike — so a
    restored slot is bit-identical however the pool is quantized. The
    dict is keyed by ``jax.tree_util.keystr`` paths so ``restore_blocks``
    can route each snapshot back to its leaf without assuming a cache
    schema. Block IDs are not part of the contract: content is addressed
    through the slot's table, so a snapshot taken from one set of blocks
    restores bitwise into any other (tests/test_paged_kv.py proves
    table-permutation invariance).
    """
    from jax.tree_util import keystr, tree_flatten_with_path

    idx = jnp.asarray(blocks, jnp.int32)
    out = {}
    for path, leaf in tree_flatten_with_path(caches)[0]:
        name = str(getattr(path[-1], "key", path[-1]))
        if name in POOL_KEYS:
            out[keystr(path)] = leaf[:, idx]
    return out


def restore_blocks(caches, blocks, snapshot: dict):
    """Scatter an ``extract_blocks`` snapshot back into the pool at the
    (possibly different) ``blocks``: pool leaves present in ``snapshot``
    get ``leaf[:, blocks] = snapshot[path]``; everything else passes
    through untouched."""
    from jax.tree_util import keystr, tree_map_with_path

    idx = jnp.asarray(blocks, jnp.int32)

    def one(path, leaf):
        snap = snapshot.get(keystr(path))
        if snap is None:
            return leaf
        return leaf.at[:, idx].set(jnp.asarray(snap, leaf.dtype))

    return tree_map_with_path(one, caches)


def concat_block_snapshots(snaps: list) -> dict:
    """Merge per-block ``extract_blocks`` snapshots (each ``{leaf path:
    [L, n_i, block_size, ...]}``) along the block axis so a multi-block
    restore is ONE ``restore_blocks`` scatter instead of one launch per
    block. The session prefix-spill tier stores one snapshot per evicted
    trie node; promoting a k-block chain concatenates k of them and pays
    a single host->device transfer + scatter."""
    if len(snaps) == 1:
        return snaps[0]
    return {k: np.concatenate([s[k] for s in snaps], axis=1)
            for k in snaps[0]}


def zero_blocks(caches, blocks):
    """Zero the listed pool blocks across every pool leaf (scale tiles
    included). Quarantine scrubbing: a numerics-guard trip releases the
    victim's blocks, and non-finite payloads must not ride along — masked
    attention multiplies masked positions by an exact 0, and ``0 * NaN``
    is NaN, so a stale NaN row would poison the block's next owner where
    ordinary stale (finite) data is harmless."""
    from jax.tree_util import tree_map_with_path

    idx = jnp.asarray(blocks, jnp.int32)

    def one(path, leaf):
        name = str(getattr(path[-1], "key", path[-1]))
        if name in POOL_KEYS:
            return leaf.at[:, idx].set(jnp.zeros((), leaf.dtype))
        return leaf

    return tree_map_with_path(one, caches)


def poison_blocks(caches, blocks):
    """NaN-fill the listed blocks in every FLOAT pool leaf (integer
    payloads keep their bits; their scale tiles take the NaN, which
    dequantizes to NaN all the same). Deterministic fault injection
    (``repro.serving.faults.FaultInjector``) uses this to model silent
    KV corruption that the serving numerics guards must catch."""
    from jax.tree_util import tree_map_with_path

    idx = jnp.asarray(blocks, jnp.int32)

    def one(path, leaf):
        name = str(getattr(path[-1], "key", path[-1]))
        if name in POOL_KEYS and jnp.issubdtype(leaf.dtype, jnp.inexact):
            return leaf.at[:, idx].set(jnp.asarray(jnp.nan, leaf.dtype))
        return leaf

    return tree_map_with_path(one, caches)


def reset_slot(caches, slot, table_row: Array):
    """Point slot ``slot`` of a batched LM cache tree at ``table_row`` and
    clear its per-slot state (len; SSM/conv state slices).

    Assumes the lm.py stacking convention: every per-slot leaf carries ONE
    leading layer-stack axis, i.e. block_table [L, B, mb], len [L, B] and
    recurrent state [L, B, ...]; pool leaves [L, nb, bs, ...] are shared
    and left untouched. (The serving engine only drives lm.py families.)
    """
    from jax.tree_util import tree_map_with_path

    def one(path, leaf):
        name = str(getattr(path[-1], "key", path[-1]))
        if name == "block_table":
            return leaf.at[:, slot, :].set(table_row[None, :])
        if name == "len":
            return leaf.at[:, slot].set(0)
        if name in POOL_KEYS:
            return leaf
        # per-slot recurrent state (SSM ssm/conv): zero the slot's slice
        return leaf.at[:, slot].set(jnp.zeros(leaf.shape[2:], leaf.dtype))

    return tree_map_with_path(one, caches)
