"""Host-memory tiers for paged KV blocks: preemption snapshots and the
session prefix spill tier.

Preemption-to-host (``KVSwap``): snapshot a victim slot's KV blocks to
host memory, restore them bitwise on re-admission. Under pool pressure
the scheduler can preempt a decoding request instead of letting the head
of the FIFO queue wait forever: the victim's pool blocks — EVERY pool
leaf, quantized payloads and their per-block scale tiles alike
(``paged.extract_blocks``) — are copied to host memory, the blocks are
released, and the slot is freed. When capacity returns, the request is
re-admitted: fresh blocks are allocated (their IDs need not match —
content is addressed through the slot's table, and table permutation is
bitwise invisible), the snapshot is scattered back
(``paged.restore_blocks``), the slot's cached length is restored to
``prefill_pos + emitted - 1`` (the last emitted token lives in the
engine's pending-token buffer, not the cache — the same bookkeeping the
verify window uses), and decoding resumes. Because every byte the
request ever computed comes back exactly, the continuation is bitwise
identical to a never-preempted run (tests/test_faults.py).

Session prefix spill (``PrefixSpill``): the same host-copy mechanics
applied to the prefix cache's EVICTED trie nodes. Eviction used to drop
a node's block — computed KV gone, the next conversation turn re-pays
the prefill. With a spill tier armed, ``PrefixCache.evict`` snapshots
each victim block (every pool leaf, scale tiles included) into this
LRU-bounded host store keyed by the node's *trie path* (the full token
prefix it encodes), and ``PrefixCache.promote`` can later page a
host-resident suffix back into fresh pool blocks when the ECM crossover
says the host-link copy beats re-prefill.

Whether restoring beats re-running prefill is that ECM crossover —
restore moves ``tokens x token_bytes`` over the host link, re-prefill
re-spends ``tokens x flops_per_token`` on the MXU — modeled in
``repro.ecm.tpu.predicted_restore_vs_reprefill``: for production-scale
models the host-link copy wins comfortably (the crossover sits around
``token_bytes * peak / host_link_bw`` FLOPs per token — a few hundred
million parameters at GQA-typical KV footprints).

Both tiers raise a typed ``SwapMissError`` when asked about a request id
/ trie path they do not hold — symmetrically, lookups and drops alike —
so a lost snapshot surfaces as a typed failure the fault layer can
reason about instead of a silent no-op masking leaked host bytes.
"""

from __future__ import annotations

from collections import OrderedDict

import numpy as np

from repro import obs
from repro.models import paged
from repro.serving.faults import SwapMissError


class KVSwap:
    """Host-memory tier for preempted requests' KV blocks.

    One snapshot per request id: ``swap_out`` gathers the listed blocks
    from every pool leaf to host numpy arrays, ``swap_in`` scatters them
    back into (possibly different) blocks and forgets the snapshot,
    ``drop`` forgets it without restoring (cancellation/expiry while
    preempted). ``swap_in`` and ``drop`` of an id with no snapshot both
    raise ``SwapMissError`` — the symmetric-raise contract (callers that
    may legitimately race a teardown check ``holds`` first)."""

    def __init__(self):
        self._store: dict[int, dict[str, np.ndarray]] = {}
        self._nblocks: dict[int, int] = {}
        # host_bytes is CURRENT residency (drops back on swap_in/drop);
        # host_bytes_total accumulates all swap-out traffic ever moved,
        # restored_bytes_total all swap-in traffic — the two directions
        # the attribution profiler prices as host-link transfers
        self.stats = {"swapped_out_blocks": 0, "restored_blocks": 0,
                      "dropped_blocks": 0, "host_bytes": 0,
                      "host_bytes_total": 0, "restored_bytes_total": 0}
        # the owning engine shares its telemetry handle; block counts
        # only in event args (bytes vary with kv_dtype)
        self.obs = obs.NULL

    def __len__(self) -> int:
        return len(self._store)

    def holds(self, rid: int) -> bool:
        return rid in self._store

    def swap_out(self, rid: int, caches, blocks: list[int]) -> None:
        assert rid not in self._store, f"request {rid} already swapped out"
        blocks_dev = paged.extract_blocks(caches, blocks)
        with obs.span("engine.fetch"):
            snap = {k: np.asarray(v) for k, v in blocks_dev.items()}
        self._store[rid] = snap
        self._nblocks[rid] = len(blocks)
        self.stats["swapped_out_blocks"] += len(blocks)
        nbytes = sum(a.nbytes for a in snap.values())
        self.stats["host_bytes"] += nbytes
        self.stats["host_bytes_total"] += nbytes
        if self.obs.enabled:
            self.obs.trace.instant("swap_out", rid=rid,
                                   blocks=len(blocks))

    def swap_in(self, rid: int, caches, blocks: list[int]):
        """Restore ``rid``'s snapshot into ``blocks`` (same count, any
        IDs); returns the updated cache tree. Raises ``SwapMissError``
        when no snapshot is held for ``rid``."""
        if rid not in self._store:
            raise SwapMissError(
                f"swap_in: no host snapshot held for request {rid}")
        snap = self._store.pop(rid)
        n = self._nblocks.pop(rid)
        assert len(blocks) == n, (
            f"request {rid}: snapshot holds {n} blocks, restore offered "
            f"{len(blocks)}")
        self.stats["restored_blocks"] += len(blocks)
        nbytes = sum(a.nbytes for a in snap.values())
        self.stats["host_bytes"] -= nbytes
        self.stats["restored_bytes_total"] += nbytes
        if self.obs.enabled:
            self.obs.trace.instant("swap_in", rid=rid, blocks=len(blocks))
        return paged.restore_blocks(caches, blocks, snap)

    def drop(self, rid: int) -> None:
        """Forget ``rid``'s snapshot without restoring. Raises
        ``SwapMissError`` for an unknown id — symmetric with ``swap_in``,
        so a teardown path that *believes* a snapshot exists cannot
        silently mask one that was already lost."""
        if rid not in self._store:
            raise SwapMissError(
                f"drop: no host snapshot held for request {rid}")
        snap = self._store.pop(rid)
        n = self._nblocks.pop(rid)
        self.stats["dropped_blocks"] += n
        self.stats["host_bytes"] -= sum(a.nbytes for a in snap.values())
        if self.obs.enabled:
            self.obs.trace.instant("swap_drop", rid=rid, blocks=n)


class PrefixSpill:
    """LRU-bounded host tier for evicted prefix-cache blocks, keyed by
    trie path.

    One entry per evicted trie node: the key is the node's full token
    prefix (root -> node, a tuple of token ids — a whole number of
    blocks), the value the host snapshot of its ONE pool block across
    every pool leaf (quantized payloads and scale tiles included).
    ``put`` runs inside ``PrefixCache.evict`` (spill instead of drop);
    ``take`` hands the snapshot to ``PrefixCache.promote`` for the
    device-side restore into a freshly allocated block. ``capacity``
    bounds host residency in blocks: an over-capacity ``put`` drops the
    least-recently-spilled entry for real (counted — the only place
    session KV still loses computed work).

    Content is position-independent (table-addressed, like ``KVSwap``
    snapshots), so a promote may land in any free block id and stays
    bitwise the original. Re-spilling an existing key overwrites it: the
    same trie path always encodes bitwise the same block content (the
    decode/prefill formulation equality in ``repro.models.attention``).
    """

    def __init__(self, capacity: int, snapshot_fn):
        assert capacity > 0, "spill tier needs a positive block capacity"
        self.capacity = capacity
        self._snapshot_fn = snapshot_fn      # blocks -> {keystr: array}
        self._store: "OrderedDict[tuple, dict[str, np.ndarray]]" = \
            OrderedDict()
        self._nbytes: dict[tuple, int] = {}
        # host_bytes is CURRENT residency; *_total are cumulative traffic
        # (spilled = device->host, promoted = host->device — the session
        # tier's two host-link directions for the attribution profiler)
        self.stats = {"spilled_blocks": 0, "promoted_blocks": 0,
                      "dropped_blocks": 0, "host_bytes": 0,
                      "spilled_bytes_total": 0, "promoted_bytes_total": 0}
        self.obs = obs.NULL

    def __len__(self) -> int:
        return len(self._store)

    def __contains__(self, key: tuple) -> bool:
        return key in self._store

    def put(self, key: tuple, block: int) -> None:
        """Snapshot ``block`` (device gather -> host copy) under ``key``,
        evicting the LRU host entry if over capacity."""
        if key in self._store:
            # same path == same bits; replace, keeping residency exact
            self._store.pop(key)
            self.stats["host_bytes"] -= self._nbytes.pop(key)
        block_dev = self._snapshot_fn([block])
        with obs.span("engine.fetch"):
            snap = {k: np.asarray(v) for k, v in block_dev.items()}
        nbytes = sum(a.nbytes for a in snap.values())
        self._store[key] = snap
        self._nbytes[key] = nbytes
        self.stats["spilled_blocks"] += 1
        self.stats["host_bytes"] += nbytes
        self.stats["spilled_bytes_total"] += nbytes
        if self.obs.enabled:
            self.obs.trace.instant("prefix_spill", tokens=len(key),
                                   resident_blocks=len(self._store))
        while len(self._store) > self.capacity:
            old, _ = self._store.popitem(last=False)
            self.stats["host_bytes"] -= self._nbytes.pop(old)
            self.stats["dropped_blocks"] += 1

    def take(self, key: tuple) -> dict[str, np.ndarray]:
        """Remove and return the snapshot for ``key`` (the caller owns
        the device restore). Raises ``SwapMissError`` for an unknown key
        — symmetric with ``KVSwap``'s miss contract."""
        if key not in self._store:
            raise SwapMissError(
                f"prefix spill tier holds no snapshot for a "
                f"{len(key)}-token path")
        snap = self._store.pop(key)
        nbytes = self._nbytes.pop(key)
        self.stats["promoted_blocks"] += 1
        self.stats["host_bytes"] -= nbytes
        self.stats["promoted_bytes_total"] += nbytes
        return snap
