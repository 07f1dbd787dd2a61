"""Paged-KV continuous-batching serving stack.

Three cooperating pieces replace the old contiguous slot-row engine:

``BlockAllocator``
    Reference-counted free-list over the shared per-layer KV block pools.
    Block 0 is the reserved null block (inactive slots point at it; stray
    writes from the batched decode land there harmlessly). A request's
    table references exactly ``ceil((len(prompt) + max_new_tokens) /
    block_size)`` blocks — short requests no longer reserve a full
    ``max_context`` row, which is the paged memory/traffic win measured in
    ``benchmarks/bench_serving.py``. Prefix caching
    (``repro.serving.prefix_cache``) shares blocks between requests and
    the radix trie, so a block returns to the free list only when its
    LAST reference is released (``alloc``/``retain``/``release``).

``Scheduler``
    FIFO admission queue (``submit`` never fails — requests wait when the
    slot pool or block pool is exhausted; head-of-line blocking is kept
    deliberately so admission order equals submission order) plus chunked
    prefill: prompts are cached ``prefill_chunk`` tokens at a time, ONE
    chunk per engine step, interleaved with the batched decode step — a
    long prompt never stalls the resident decode batch for more than one
    chunk's latency (the old engine ran whole-prompt batch-1 prefill
    between decode steps).

``DecodeEngine``
    Owns the jitted model functions and the device cache tree, drives the
    scheduler, and keeps the fused ``_logit_stats`` pass: one batched
    reduction-engine launch per step yields every slot's chosen-token
    logprob, logsumexp and logit health statistics — only (B,)-sized
    arrays ever reach the host.

``prefix_cache=True`` adds the radix layer
(``repro.serving.prefix_cache``): admission walks a block-granular trie
over the prompt, maps the hit prefix's pool blocks into the slot's table
(copy-on-write at a mid-block divergence), starts chunked prefill at the
first uncached token, and retirement inserts the completed prompt prefix
for later requests — with LRU eviction of unreferenced trie leaves when
the free list runs short.

Determinism: greedy argmax by default; a request's chunk boundaries and
decode math depend only on its own prompt and the cache geometry, so
batched serving matches solo generation token-for-token
(tests/test_serving.py, tests/test_paged_kv.py) and a prefix-cache hit
is bitwise its cold run (tests/test_prefix_cache.py). Requests can opt
into temperature + top-k sampling with a per-request ``seed``; the
sampling stream is keyed on (seed, tokens emitted) only, so it too is
independent of batch composition and admission timing.

Fault tolerance (``repro.serving.faults`` / ``repro.serving.swap``):
requests carry ``deadline_steps`` and can be cancelled mid-flight with
every reference (slot, refcounted blocks, proposer mirror state)
released correctly; under pool pressure the scheduler can PREEMPT a
decoding victim — its blocks snapshot to host memory (``KVSwap``) and
restore bitwise on re-admission, so a preempted request's output is
identical to a never-preempted run; a ``NumericsGuard`` watches the
fused logit stats every step and quarantines (rather than crashes or
poisons) a slot whose logits go non-finite or whose compensated-vs-naive
sum deviation explodes; and allocator/scheduler failures raise typed,
recoverable exceptions (``AllocatorError``/``AdmissionError``) the
admission path absorbs. All of it is exercised by the keyed, replayable
``FaultInjector`` in tests/test_faults.py.
"""

from __future__ import annotations

import functools
import time
from collections import deque
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.kernels import ops
from repro.models import api, paged
from repro.models.config import ModelConfig
from repro.models.paged import NULL_BLOCK, PagedLayout
from repro.serving.faults import (AdmissionError, AllocatorError,
                                  NumericsGuard, ProposerStallError,
                                  StallError)
from repro.serving.prefix_cache import PrefixCache, PrefixMatch
from repro.serving.swap import KVSwap, PrefixSpill

DEFAULT_BLOCK_SIZE = paged.DEFAULT_BLOCK_SIZE


@dataclass
class Request:
    rid: int
    prompt: list
    max_new_tokens: int
    eos_id: int | None = None
    # sampling knobs: temperature == 0 keeps the deterministic greedy path;
    # top_k == 0 means the full vocabulary; ``seed`` keys this request's
    # private sampling stream (folded with the emit index, so the draw is
    # independent of batch composition).
    temperature: float = 0.0
    top_k: int = 0
    seed: int = 0
    # speculative decoding: None inherits the engine's spec_k; the engine
    # additionally caps by its verify-window width, the request's remaining
    # token budget, and the slot's allocated blocks
    spec_k: int | None = None
    output: list = field(default_factory=list)
    logprobs: list = field(default_factory=list)   # per emitted token
    slot: int | None = None
    done: bool = False
    prefill_pos: int = 0                           # prompt tokens cached
    blocks: list = field(default_factory=list)     # pool blocks referenced
    # prefix caching: tokens served from the radix trie at admission
    # (prefill starts at the first uncached token) and, transiently, the
    # shared block awaiting its copy-on-write copy
    prefix_hit: int = 0
    cow_src: int | None = None
    # lifecycle: deadline_steps bounds the request's wall-clock in ENGINE
    # STEPS from submission (None = no deadline); priority feeds the
    # "priority" preemption victim policy (higher survives). ``state``
    # walks queued -> prefilling -> decoding (-> preempted -> decoding)*
    # -> done | cancelled | expired | quarantined | failed.
    deadline_steps: int | None = None
    priority: int = 0
    state: str = "queued"
    error: str | None = None
    submit_step: int = 0
    last_progress_step: int = 0
    # the same three moments on the host clock (``time.perf_counter``
    # seconds), once per run: submission, admission into a slot, and the
    # dispatch of the first prefill chunk. A restore keeps them.
    submit_time: float | None = None
    admit_time: float | None = None
    first_chunk_time: float | None = None
    admit_seq: int = -1
    retries: int = 0

    @property
    def num_cached(self) -> int:
        """Tokens currently occupying KV positions (prompt + emitted)."""
        return self.prefill_pos + len(self.output)

    def reset_for_retry(self) -> None:
        """Scrub per-run state so the request can be resubmitted (the
        FailoverServer's degraded-tier retry path)."""
        assert self.slot is None and not self.blocks, \
            "reset of a request still holding engine resources"
        self.output = []
        self.logprobs = []
        self.done = False
        self.prefill_pos = 0
        self.prefix_hit = 0
        self.cow_src = None
        self.state = "queued"
        self.submit_time = self.admit_time = self.first_chunk_time = None
        self.admit_seq = -1
        self.retries += 1


class BlockAllocator:
    """Reference-counted LIFO free-list over a ``num_blocks`` pool; block 0
    stays reserved.

    Prefix caching shares blocks between live requests and the radix
    trie, so ownership is a count, not a holder: ``alloc`` hands out
    blocks at refcount 1, every additional sharer ``retain``s, and a
    block rejoins the free list only when ``release`` drops the count to
    zero. Every misuse — exhaustion, double free, retain of a free block
    — raises the typed, recoverable ``AllocatorError`` (the admission
    path catches it and lets the head wait); the Hypothesis
    interleavings in tests/test_prefix_cache.py drive these invariants.

    ``fail_next`` is the deterministic-fault hook: when armed (by a
    ``FaultInjector``), the next ``alloc`` raises ``AllocatorError``
    once — modeling a transient allocation failure the engine must
    absorb, not crash on.
    """

    def __init__(self, num_blocks: int):
        assert num_blocks >= 2, "pool needs the null block plus capacity"
        self.num_blocks = num_blocks
        self._free = list(range(num_blocks - 1, NULL_BLOCK, -1))
        self._ref: dict[int, int] = {}
        self.fail_next = False
        self.faults = 0

    @property
    def num_free(self) -> int:
        return len(self._free)

    @property
    def num_held(self) -> int:
        """Distinct blocks with at least one live reference."""
        return len(self._ref)

    def refcount(self, block: int) -> int:
        return self._ref.get(block, 0)

    def alloc(self, n: int) -> list[int]:
        if self.fail_next:
            self.fail_next = False
            self.faults += 1
            raise AllocatorError("injected allocation failure")
        if n > len(self._free):
            raise AllocatorError(f"block pool exhausted: want {n}, "
                                 f"have {len(self._free)}")
        blocks = [self._free.pop() for _ in range(n)]
        for b in blocks:
            self._ref[b] = 1
        return blocks

    def retain(self, blocks: list[int]) -> None:
        for b in blocks:
            if b not in self._ref:
                raise AllocatorError(f"retain of free block {b}")
            self._ref[b] += 1

    def release(self, blocks: list[int]) -> None:
        for b in blocks:
            if b not in self._ref:
                raise AllocatorError(f"double free of block {b}")
            self._ref[b] -= 1
            if self._ref[b] == 0:
                del self._ref[b]
                self._free.append(b)

    # back-compat alias: a sole-owner release IS a free
    free = release


class Scheduler:
    """FIFO admission + slot assignment + chunked-prefill bookkeeping."""

    def __init__(self, allocator: BlockAllocator, max_slots: int,
                 layout: PagedLayout, prefill_chunk: int,
                 prefix_cache: PrefixCache | None = None,
                 session_kv: bool = True):
        self.allocator = allocator
        self.layout = layout
        self.prefill_chunk = prefill_chunk
        self.prefix_cache = prefix_cache
        # session KV: retirement caches prompt + emitted output (the
        # full conversation history), not just the prompt
        self.session_kv = session_kv
        self.waiting: deque[Request] = deque()
        self.prefilling: deque[Request] = deque()
        self.decoding: dict[int, Request] = {}
        self.preempted: deque[Request] = deque()
        self._free_slots = list(range(max_slots))
        self._admit_seq = 0

    def submit(self, req: Request) -> None:
        need = len(req.prompt) + req.max_new_tokens
        if need > self.layout.max_context:
            raise AdmissionError(
                f"request {req.rid}: prompt+max_new = {need} exceeds "
                f"max_context {self.layout.max_context}")
        usable = self.allocator.num_blocks - 1          # minus null block
        if self.blocks_needed(req) > usable:
            # would head-block the FIFO queue forever on an oversubscribed
            # pool — reject at submission, not livelock at admission
            raise AdmissionError(
                f"request {req.rid}: needs {self.blocks_needed(req)} blocks "
                f"but the pool only has {usable}")
        if req.deadline_steps is not None and req.deadline_steps < 1:
            raise AdmissionError(
                f"request {req.rid}: deadline_steps must be >= 1, "
                f"got {req.deadline_steps}")
        req.state = "queued"
        self.waiting.append(req)

    def blocks_needed(self, req: Request) -> int:
        return self.layout.blocks_for(len(req.prompt) + req.max_new_tokens)

    def _match_candidates(self, req: Request) -> list[PrefixMatch]:
        """Admission plans for ``req``, best hit first. A COW hit pins
        ONE block more than the request's own budget (the shared source
        of the copy), so a request sized at the pool's full capacity can
        be un-admittable under its best match while perfectly admittable
        under a degraded one — each fallback pins strictly less: drop
        the COW (block-aligned hit only), then go fully cold (every trie
        block becomes evictable). The cold plan needs exactly
        ``blocks_needed`` <= usable pool (the submit() guarantee), which
        is what keeps the PR-2 no-livelock contract intact."""
        if self.prefix_cache is None:
            return [PrefixMatch()]
        pc = self.prefix_cache
        m = pc.match(req.prompt)
        # Session spill tier: page the longest host-resident continuation
        # of this prompt back into free pool blocks (ECM-gated inside
        # promote), then RE-match so the ordinary full/COW/cold plan
        # logic sees the promoted nodes as resident trie content.
        # Promotion converts would-be-fresh blocks into shared ones
        # one-for-one, so it never makes the admission harder.
        if pc.spill is not None and pc.promote(req.prompt, rid=req.rid):
            m = pc.match(req.prompt)
        cands = [m]
        if m.cow_src is not None:
            cands.append(PrefixMatch(m.blocks,
                                     len(m.blocks) * self.layout.block_size,
                                     None))
        if m.blocks:
            cands.append(PrefixMatch())
        return cands

    def _try_admit(self, req: Request, match: PrefixMatch) -> bool:
        """One admission attempt under one match plan: retain the shared
        blocks FIRST (so eviction — from this attempt or a later request
        in the same sweep — can never take them), evict unreferenced
        trie leaves if the remainder doesn't fit, and either allocate or
        roll the retains back."""
        if self.prefix_cache is not None:
            self.allocator.retain(match.blocks)
            if match.cow_src is not None:
                self.allocator.retain([match.cow_src])
        need = self.blocks_needed(req) - len(match.blocks)
        if need > self.allocator.num_free:
            if self.prefix_cache is not None:
                self.prefix_cache.evict(need - self.allocator.num_free)
            if need > self.allocator.num_free:
                if self.prefix_cache is not None:
                    self.allocator.release(match.blocks)
                    if match.cow_src is not None:
                        self.allocator.release([match.cow_src])
                return False
        try:
            fresh = self.allocator.alloc(need)
        except AllocatorError:
            # transient allocation failure (e.g. injected): roll the
            # protective retains back and let the head wait — the FIFO
            # contract survives, nothing crashes
            if self.prefix_cache is not None:
                self.allocator.release(match.blocks)
                if match.cow_src is not None:
                    self.allocator.release([match.cow_src])
            return False
        req.blocks = match.blocks + fresh
        req.prefix_hit = match.hit
        req.cow_src = match.cow_src       # engine copies, then releases
        req.prefill_pos = match.hit       # first uncached token
        if self.prefix_cache is not None:
            self.prefix_cache.note_admitted(match.hit, len(req.prompt),
                                            match.cow_src is not None,
                                            rid=req.rid)
        return True

    def admit(self) -> list[Request]:
        """Move waiting requests into slots while capacity lasts. Strict
        FIFO: the queue head blocks (no skip-ahead), so completion of
        equal-length requests follows submission order.

        With a prefix cache attached, admission tries the head's match
        plans best-first (full hit incl. COW, block-aligned hit, cold —
        see ``_match_candidates``); if even the cold plan cannot be
        covered after evicting unreferenced trie leaves, the head waits
        — admission order is preserved and retirement of live requests
        (whose blocks no eviction can touch) eventually unblocks it, so
        an oversubscribed pool still never livelocks.
        """
        admitted = []
        while self.waiting and self._free_slots:
            req = self.waiting[0]
            if not any(self._try_admit(req, m)
                       for m in self._match_candidates(req)):
                break
            self.waiting.popleft()
            req.slot = self._free_slots.pop()
            req.state = "prefilling"
            req.admit_time = time.perf_counter()
            req.admit_seq = self._admit_seq
            self._admit_seq += 1
            self.prefilling.append(req)
            admitted.append(req)
        # Preempted requests re-admit only once the waiting queue has
        # drained: a restore that displaced the very request whose
        # admission forced the preemption would swap-thrash forever.
        # They need no prefix match — their content comes back verbatim
        # from the host snapshot (the engine's restore path).
        while not self.waiting and self.preempted and self._free_slots:
            req = self.preempted[0]
            need = self.blocks_needed(req)
            if need > self.allocator.num_free and self.prefix_cache:
                self.prefix_cache.evict(need - self.allocator.num_free)
            if need > self.allocator.num_free:
                break
            try:
                req.blocks = self.allocator.alloc(need)
            except AllocatorError:
                break
            self.preempted.popleft()
            req.slot = self._free_slots.pop()
            req.admit_seq = self._admit_seq
            self._admit_seq += 1
            admitted.append(req)      # engine restores, then start_decoding
        return admitted

    def next_chunk(self) -> tuple[Request, list, int] | None:
        """The head prefilling request's next chunk (req, tokens, pos0)."""
        if not self.prefilling:
            return None
        req = self.prefilling[0]
        pos0 = req.prefill_pos
        return req, req.prompt[pos0:pos0 + self.prefill_chunk], pos0

    def prefill_advance(self, req: Request, n: int) -> bool:
        """Record ``n`` freshly cached prompt tokens; True when complete."""
        req.prefill_pos += n
        if req.prefill_pos == len(req.prompt):
            self.prefilling.popleft()
            return True
        return False

    def start_decoding(self, req: Request) -> None:
        req.state = "decoding"
        self.decoding[req.slot] = req

    def preempt(self, req: Request) -> None:
        """Bookkeeping half of preemption-to-host (the engine snapshots
        the blocks FIRST): drop the victim from the decode batch, release
        its blocks (trie-shared ones survive via their refcounts) and
        free the slot; the request queues for re-admission."""
        assert req.slot in self.decoding, "only decoding requests preempt"
        self.decoding.pop(req.slot)
        self.allocator.release(req.blocks)
        req.blocks = []
        self._free_slots.append(req.slot)
        req.slot = None
        req.state = "preempted"
        self.preempted.append(req)

    def drop(self, req: Request, state: str) -> bool:
        """Remove ``req`` from whichever queue holds it (cancellation /
        deadline expiry), releasing slot + refcounted blocks. Returns
        False if the request is not in flight (already done/terminated).
        The caller owns device-side cleanup (table reset, swap drop)."""
        if req in self.waiting:
            self.waiting.remove(req)
        elif req in self.preempted:
            self.preempted.remove(req)
        elif req.slot is not None and (req in self.prefilling
                                       or self.decoding.get(req.slot) is req):
            if req in self.prefilling:
                self.prefilling.remove(req)
            self.decoding.pop(req.slot, None)
            # no trie insert: a partial/cancelled prompt is not a prefix
            # other requests should trust
            self.allocator.release(req.blocks)
            req.blocks = []
            self._free_slots.append(req.slot)
        else:
            return False
        req.state = state
        return True

    def retire(self, req: Request) -> None:
        req.done = True
        req.state = "done"
        self.decoding.pop(req.slot, None)
        if self.prefix_cache is not None:
            # cache the request's tokens BEFORE releasing: new trie nodes
            # retain their blocks, so they survive the request's release;
            # deduped spans just release through. Session KV caches the
            # FULL history — prompt plus emitted output — so turn N+1
            # (which resubmits this prompt + this reply) hits on its
            # whole history, not just the old prompt. Only the first
            # len(output)-1 output tokens are cache-resident: the final
            # emitted token is still pending in the engine's next-token
            # buffer, never written to KV.
            seq = req.prompt
            if self.session_kv and req.output:
                n_valid = len(req.prompt) + len(req.output) - 1
                seq = (list(req.prompt) + list(req.output))[:n_valid]
            self.prefix_cache.insert(seq, req.blocks)
        self.allocator.release(req.blocks)
        req.blocks = []
        self._free_slots.append(req.slot)

    @property
    def num_unfinished(self) -> int:
        return (len(self.waiting) + len(self.prefilling)
                + len(self.decoding) + len(self.preempted))


@jax.jit
def _logit_stats(logits: jax.Array, tokens: jax.Array
                 ) -> dict[str, jax.Array]:
    """Per-row logit statistics for the whole batch in ONE fused engine
    pass: running max (for the stable logsumexp), compensated sum and
    sum-of-squares (mean / RMS health metrics). The logits cross memory
    once for all four statistics instead of once per jnp reduction.

    ``tokens`` (B,) selects each row's chosen token; the logprob gather
    happens device-side so only (B,)-sized results ever reach the host.

    ``round_off`` is the in-band numerical-fault detector
    (``repro.serving.faults.NumericsGuard``): the relative deviation
    between the engine's compensated row sum and a naive float32 sum of
    the same row — i.e. the naive stream's accumulated round-off, the
    quantity Dukhan & Vondele's round-off-instruction proposal would
    expose in hardware. Healthy rows sit near float32 epsilon;
    catastrophic cancellation or corrupted logits push it orders of
    magnitude higher.
    """
    l32 = logits.astype(jnp.float32)
    st = ops.batched_fused_reduce(l32, outputs=("max", "sum", "sumsq"))
    # Second (transformed) pass for the exp-sum: logsumexp = m + log Σe^(l-m).
    sumexp = ops.batched_fused_reduce(
        jnp.exp(l32 - st["max"][:, None]), outputs=("sum",))["sum"]
    lse = st["max"] + jnp.log(sumexp)
    chosen = jnp.take_along_axis(l32, tokens[:, None], axis=-1)[:, 0]
    vocab = logits.shape[-1]
    naive = jnp.sum(l32, axis=-1)
    return {"logprob": chosen - lse, "logsumexp": lse, "max": st["max"],
            "mean": st["sum"] / vocab,
            "rms": jnp.sqrt(st["sumsq"] / vocab),
            "round_off": jnp.abs(st["sum"] - naive)
            / (jnp.abs(st["sum"]) + 1.0)}


@jax.jit
def _greedy_tokens(rows: jax.Array) -> jax.Array:
    """Batched greedy choice as ONE cached launch — an eager ``argmax`` +
    ``astype`` here would pay two uncached dispatches per decode step."""
    return jnp.argmax(rows, axis=-1).astype(jnp.int32)


# host-transfer order of the decode step's packed stats row block (the
# engine's fused decode launch stacks [tokens] + these six)
_STAT_KEYS = ("logprob", "logsumexp", "max", "mean", "rms", "round_off")


def _sample_row(row: jax.Array, temperature: jax.Array, key: jax.Array,
                top_k: int) -> jax.Array:
    """Temperature + top-k draw from one logit row (vmapped below)."""
    logits = row.astype(jnp.float32) / jnp.maximum(temperature, 1e-6)
    if top_k:
        # clamp: top_k beyond the vocab means "no truncation", not a crash
        kth = jax.lax.top_k(logits, min(top_k, logits.shape[-1]))[0][..., -1]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    return jax.random.categorical(key, logits)


@functools.partial(jax.jit, static_argnames=("top_k",))
def _sample_rows(rows: jax.Array, temperatures: jax.Array, keys: jax.Array,
                 top_k: int) -> jax.Array:
    """One launch draws every sampled slot that shares a ``top_k``: rows
    [S, V], temperatures [S], keys [S] -> tokens [S]. Keeps the decode hot
    loop's one-launch discipline — only the chosen indices cross to the
    host, however many requests are sampling."""
    return jax.vmap(lambda r, t, k: _sample_row(r, t, k, top_k))(
        rows, temperatures, keys)


def fused_decode_fn(cfg: ModelConfig):
    """The engine's one launch per decode step:
    (params, (tokens [B, 1] int32, live [B] bool), caches)
        -> (rows [B, V], packed [1 + 6, B] f32, caches).

    ``live`` marks the decoding rows. Only they advance: every other row
    (free, retired, mid-prefill) keeps its ``len`` and recurrent state,
    so the paged-attention kernel walks one block for an idle row rather
    than a length that grows by one every step, and a prefilling row
    stays at its chunk progress. ``live`` rides beside the tokens, so
    code that wraps the launch keeps its three-operand call.
    """
    decode_raw = api.decode_fn(cfg)

    def _decode_fused(params, feed, caches):
        # Model step + held rows + greedy choice + the fused _logit_stats
        # metrics, packed [1 + 6, B] f32 so a single host transfer
        # carries everything the scheduler reads. Separate jit calls for
        # choice/stats plus one sync per stat array cost ~25% of a CPU
        # decode step. Token ids ride the f32 packing exactly (vocab <<
        # 2^24). ``rows`` comes back device-side, untransferred, for the
        # sampling / fault-injection override paths.
        tokens, live = feed
        logits, new_caches = decode_raw(params, tokens, caches)
        new_caches = paged.keep_slots(caches, new_caches, ~live)
        rows = logits.reshape(logits.shape[0], -1)
        toks = jnp.argmax(rows, axis=-1).astype(jnp.int32)
        stats = _logit_stats(rows, toks)
        packed = jnp.stack([toks.astype(jnp.float32)]
                           + [stats[k] for k in _STAT_KEYS])
        return rows, packed, new_caches

    return _decode_fused


class DecodeEngine:
    """Paged continuous-batching engine over a fixed slot pool.

    ``num_blocks`` sets the shared pool size per layer (default: full
    capacity — every slot could hold ``max_context``); passing a smaller
    pool oversubscribes slots against blocks and the scheduler's admission
    gate enforces real availability.

    ``preempt`` arms preemption-to-host under pool pressure: when the
    FIFO head cannot be admitted, a decoding victim's blocks snapshot to
    host memory (``KVSwap``) and it re-admits bitwise later. ``"lru"``
    picks the most recently admitted victim (least completed work to
    redo), ``"priority"`` the lowest ``Request.priority`` strictly below
    the head's. ``guard`` (default on) is the per-step logit health
    check; ``fault_injector`` arms the keyed fault-injection harness.

    Session KV (needs ``prefix_cache=True``): ``session_kv`` (default on)
    caches a retired request's full token history — prompt plus emitted
    output — so a multi-turn conversation's next turn hits on everything
    already computed. ``spill_blocks`` arms the host spill tier: evicted
    trie blocks snapshot to host (``PrefixSpill``, capacity in blocks;
    0 keeps plain drop-on-evict) and promote back into free pool blocks
    when the ECM restore-vs-reprefill forecast favors the host link.
    ``promote`` picks that gate: ``"auto"`` evaluates
    ``repro.ecm.tpu.predicted_restore_vs_reprefill`` on this engine's
    KV geometry and parameter count, ``"always"``/``"never"`` force it
    (toy test models sit far below the TPU-modeled crossover, so tests
    and CPU demos use ``"always"``).
    """

    PREEMPT_POLICIES = ("off", "lru", "priority")
    PROMOTE_MODES = ("auto", "always", "never")

    def __init__(self, cfg: ModelConfig, params, *, max_slots: int = 4,
                 max_context: int = 256,
                 block_size: int = DEFAULT_BLOCK_SIZE,
                 num_blocks: int | None = None, prefill_chunk: int = 32,
                 prefix_cache: bool = False, session_kv: bool = True,
                 spill_blocks: int = 0, promote: str = "auto",
                 preempt: str = "off",
                 guard: NumericsGuard | None = NumericsGuard(),
                 fault_injector=None, telemetry: obs.Telemetry | None = None):
        assert cfg.family in ("dense", "moe", "ssm", "vlm"), cfg.family
        if prefix_cache and cfg.family == "ssm":
            raise ValueError(
                "prefix caching shares paged KV blocks; the 'ssm' family "
                "carries constant-size recurrent state with no per-token "
                "KV to share")
        if preempt not in self.PREEMPT_POLICIES:
            raise ValueError(f"preempt must be one of "
                             f"{self.PREEMPT_POLICIES}, got {preempt!r}")
        if promote not in self.PROMOTE_MODES:
            raise ValueError(f"promote must be one of "
                             f"{self.PROMOTE_MODES}, got {promote!r}")
        if spill_blocks and not prefix_cache:
            raise ValueError(
                "spill_blocks arms the prefix-cache host spill tier and "
                "needs prefix_cache=True")
        if preempt != "off" and cfg.family == "ssm":
            raise ValueError(
                "preemption snapshots paged KV blocks; the 'ssm' family "
                "carries recurrent state that cannot be swapped out "
                "block-wise")
        self.cfg = cfg
        self.params = params
        self.max_slots = max_slots
        self.kv = api.KVCache.build(cfg, max_context=max_context,
                                    block_size=block_size,
                                    max_slots=max_slots,
                                    num_blocks=num_blocks)
        self.layout = self.kv.layout
        allocator = BlockAllocator(self.kv.num_blocks)
        self.prefix_cache = (PrefixCache(allocator, self.layout.block_size)
                             if prefix_cache else None)
        self.scheduler = Scheduler(allocator, max_slots, self.layout,
                                   prefill_chunk,
                                   prefix_cache=self.prefix_cache,
                                   session_kv=session_kv)
        self.preempt_policy = preempt
        self.guard = guard
        self.injector = fault_injector
        self.swap = KVSwap()
        self.quarantined: list[Request] = []
        self._step_count = 0
        # Telemetry: every hook below guards on ``self.obs.enabled`` so a
        # plain engine (the default NULL recorder) runs the untouched
        # one-launch/one-transfer hot path. Collaborating components get
        # the SAME handle — one step clock, one event stream.
        self.obs = telemetry if telemetry is not None else obs.NULL
        self.swap.obs = self.obs
        if self.prefix_cache is not None:
            self.prefix_cache.obs = self.obs
        if fault_injector is not None:
            fault_injector.obs = self.obs
        if self.obs.enabled:
            m = self.obs.metrics
            self._h_ttft = m.histogram(
                "ttft_steps", unit="steps",
                help="engine steps from submit to first emitted token")
            self._h_queue_wait = m.histogram(
                "queue_wait_steps", unit="steps",
                help="engine steps from submit to slot admission")

        self._prefill_chunk = jax.jit(api.prefill_chunk_fn(cfg))
        self._decode = jax.jit(fused_decode_fn(cfg))
        self._reset_slot = jax.jit(paged.reset_slot)
        self._set_lens = jax.jit(paged.set_lens)
        self._copy_block = jax.jit(paged.copy_block)

        self.caches = self.kv.init(max_slots)
        # host-side: slots mutate one int per emitted token, and an
        # eager device scatter per token costs more than the whole
        # decode launch on CPU — upload once per step instead
        self._next_tokens = np.zeros((max_slots, 1), np.int32)
        # the decode launch's ``live`` row mask and the decoding slots it
        # was built from: uploaded again only when that set changes
        self._live = None
        self._live_slots = None
        # The all-NULL table row every slot teardown points back at.
        # Built ONCE: retire/terminate/preempt/quarantine sit on the hot
        # path, and rebuilding this constant per retirement costs a fresh
        # host->device upload each time for identical bytes.
        self._null_row = jnp.full((self.layout.max_blocks,), NULL_BLOCK,
                                  jnp.int32)

        # ECM-style KV traffic accounting: the bytes each LAYOUT must
        # address per step (paged: the slot's allocated blocks; contiguous:
        # a fixed max_context row). This is the analytic bound the paper's
        # methodology predicts and the TPU decode kernel realizes; the
        # XLA gather fallback (CPU decode, chunk prefill) materializes
        # full virtual rows and is not what this counter measures.
        # All-zero for constant-state (SSM) families — no per-token KV.
        # ``paged_bytes_bf16`` re-prices the SAME touched tokens at bf16
        # pool rates: paged_bytes_bf16 / paged_bytes is the measured-
        # workload KV-traffic reduction of a quantized ``cfg.kv_dtype``
        # (benchmarks/bench_quant.py compares it against the ECM
        # prediction in repro.ecm.tpu.predicted_decode_speedup).
        self._token_bytes = self.kv.token_bytes(max_slots)
        self._token_bytes_bf16 = api.KVCache.build(
            cfg.with_(kv_dtype="bf16"), max_context=max_context,
            block_size=block_size, max_slots=max_slots,
            num_blocks=num_blocks).token_bytes(max_slots)
        # Prefix-caching counters (always present; stay zero when the
        # cache is off): ``prefix_saved_bytes`` prices the KV store
        # traffic the hit prefixes never re-moved — hit tokens at the
        # engine's per-token pool bytes, the same unit as paged_bytes —
        # and ``prefix_hit_tokens / prefix_prompt_tokens`` is the hit
        # rate repro.ecm.tpu.predicted_prefill_speedup forecasts from.
        # Fault-tolerance counters ride the same dict: preempted /
        # restored_blocks / guard_trips are the bench_serving trajectory
        # columns. Per-request stall diagnostics travel on StallError
        # (and as ``stall`` trace events when telemetry is attached).
        self.kv_stats = {"paged_bytes": 0, "paged_bytes_bf16": 0,
                         "contiguous_bytes": 0,
                         "decode_steps": 0, "prefill_chunks": 0,
                         "prefill_tokens": 0,
                         "prefix_hit_tokens": 0, "prefix_prompt_tokens": 0,
                         "prefix_saved_bytes": 0, "prefix_cow_blocks": 0,
                         "prefix_evicted_blocks": 0,
                         "prefix_spilled_blocks": 0,
                         "prefix_spilled_bytes": 0,
                         "prefix_promoted_blocks": 0,
                         "prefix_promoted_tokens": 0,
                         "preempted": 0, "preempted_blocks": 0,
                         "restored_blocks": 0, "guard_trips": 0,
                         "cancelled": 0, "expired": 0, "alloc_faults": 0,
                         "stalled_requests": 0, "held_rows": 0}

        # Session spill tier: evicted trie blocks snapshot to host and
        # can promote back into free blocks. Armed last — the snapshot
        # closure reads the LIVE cache tree, and the auto promote gate
        # prices restore vs re-prefill on this engine's KV geometry.
        if self.prefix_cache is not None and spill_blocks:
            spill = PrefixSpill(
                spill_blocks,
                lambda blocks: paged.extract_blocks(self.caches, blocks))
            spill.obs = self.obs
            self.prefix_cache.spill = spill
            self.prefix_cache.promote_fn = self._promote_restore
            self.prefix_cache.promote_ratio = self._promote_gate(promote)

    # ------------------------------------------------------------ API -----

    def submit(self, req: Request) -> None:
        """Enqueue a request. Never fails on a full slot/block pool — the
        scheduler admits FIFO as capacity frees up. Raises
        ``AdmissionError`` for requests that could NEVER run (context
        overflow, pool oversubmit, bad deadline)."""
        req.submit_step = self._step_count
        req.last_progress_step = self._step_count
        req.submit_time = time.perf_counter()
        self.scheduler.submit(req)
        if self.obs.enabled:
            self.obs.trace.begin("queued", rid=req.rid,
                                 prompt_tokens=len(req.prompt),
                                 max_new=req.max_new_tokens)

    # the host span around the batched decode (``repro.obs.span``)
    _DECODE_SPAN = "engine.decode_step"

    def step(self) -> None:
        """One engine step: expire deadlines, admit (preempting a victim
        to host under pool pressure if armed), run at most one prefill
        chunk, then one batched decode step for every decoding slot.

        Host phase spans (``repro.obs.span``) tile the step on the
        profiler's clock: ``engine.admit``, ``engine.prefill_chunk``,
        ``engine.first_token``, the decode span and ``engine.retire``
        (inside the phase that retires), with each host read that waits
        on the device in an ``engine.fetch``."""
        self._step_count += 1
        if self.obs.enabled:
            self.obs.set_step(self._step_count)
        with obs.span("engine.admit"):
            self._admit()
        nxt = self.scheduler.next_chunk()
        if nxt is not None:
            req, chunk, pos0 = nxt
            with obs.span("engine.prefill_chunk"):
                logits = self._dispatch_chunk(req, chunk, pos0)
            if logits is not None:
                with obs.span("engine.first_token"):
                    self._emit_first_token(req, logits)
        if self.scheduler.decoding:
            with obs.span(self._DECODE_SPAN):
                self._decode_step()
        self.kv_stats["alloc_faults"] = self.scheduler.allocator.faults

    def _admit(self) -> None:
        self._expire_deadlines()
        if self.injector is not None:
            self._inject_step_faults()
        admitted = self.scheduler.admit()
        if self.preempt_policy != "off":
            # pool pressure: the FIFO head couldn't be admitted — swap a
            # decoding victim's blocks to host and retry (bounded by the
            # slot count; each spin shrinks the decode batch by one)
            spins = 0
            while (self.scheduler.waiting and self.scheduler.decoding
                   and spins < self.max_slots and self._preempt_for_head()):
                spins += 1
                admitted += self.scheduler.admit()
        for req in admitted:
            if req.state == "preempted":
                self._restore(req)
            else:
                self._admit_slot(req)

    def _dispatch_chunk(self, req: Request, chunk: list, pos0: int):
        """Dispatch one prefill chunk of ``req``; returns the chunk's
        logits when it completes the prompt, else None."""
        first = pos0 == req.prefix_hit
        if first:
            req.first_chunk_time = time.perf_counter()
        # profiling is opt-in (Telemetry(profile=True)); the prof
        # guard keeps the disabled/plain-telemetry hot path untouched
        prof = self.obs.profile
        t0 = time.perf_counter() if prof is not None else 0.0
        tok_arr = jnp.asarray([chunk], jnp.int32)
        logits, self.caches = self._prefill_chunk(
            self.params, tok_arr, self.caches,
            jnp.int32(req.slot), jnp.int32(pos0))
        if prof is not None:
            with obs.span("engine.fetch"):
                logits.block_until_ready()
            # lower against the POST-call cache tree: the update is
            # functional, so shapes (the HLO-cost cache key) match
            # the consumed input tree exactly
            prof.record_call(
                "prefill_chunk", self._prefill_chunk,
                (self.params, tok_arr, self.caches,
                 jnp.int32(req.slot), jnp.int32(pos0)),
                wall_s=time.perf_counter() - t0,
                host_bytes=tok_arr.nbytes)
        self._on_prefill_chunk(req, chunk, pos0)
        if self.obs.enabled:
            self.obs.trace.instant("prefill_chunk", rid=req.rid,
                                   pos0=pos0, tokens=len(chunk))
        req.last_progress_step = self._step_count
        # tokens the engine ACTUALLY pushed through the prefill path:
        # the measured side of the prefix-cache reduction (a cold
        # engine accumulates every prompt token here, a hit engine
        # only the uncached remainder)
        self.kv_stats["prefill_tokens"] += len(chunk)
        self._account_prefill(pos0 + len(chunk), first=first)
        if self.scheduler.prefill_advance(req, len(chunk)):
            return logits
        return None

    def _admit_slot(self, req: Request) -> None:
        """Device-side half of a fresh admission: point the slot's table
        at the request's blocks, run the COW copy, pre-set the prefix-hit
        length, mirror prefix stats."""
        row = np.full((self.layout.max_blocks,), NULL_BLOCK, np.int32)
        row[:len(req.blocks)] = req.blocks
        self.caches = self._reset_slot(self.caches,
                                       jnp.int32(req.slot),
                                       jnp.asarray(row))
        if req.cow_src is not None:
            # copy-on-write at the divergence block: the request's
            # table already points at the fresh copy target; fill it
            # from the shared block, then drop the admission-time
            # protective reference on the source
            dst = req.blocks[req.prefix_hit // self.layout.block_size]
            self.caches = self._copy_block(self.caches,
                                           jnp.int32(req.cow_src),
                                           jnp.int32(dst))
            self.scheduler.allocator.release([req.cow_src])
            req.cow_src = None
        if req.prefix_hit:
            # Pre-set the slot's cached length to the hit: readers
            # mask correctly from the first chunk, and the batched
            # decode's stray write for this mid-prefill slot lands at
            # the request's OWN first writable position — never
            # inside a shared block.
            self.caches = self._set_lens(
                self.caches, jnp.asarray([req.slot], jnp.int32),
                jnp.asarray([req.prefix_hit], jnp.int32))
        if self.prefix_cache is not None:
            # one source of truth: PrefixCache.stats (fed by
            # note_admitted/evict) — the engine only mirrors, and
            # prices hit tokens at its per-token pool bytes
            cs = self.prefix_cache.stats
            self.kv_stats.update(
                prefix_hit_tokens=cs["hit_tokens"],
                prefix_prompt_tokens=cs["prompt_tokens"],
                prefix_cow_blocks=cs["cow_blocks"],
                prefix_evicted_blocks=cs["evicted_blocks"],
                prefix_promoted_blocks=cs["promoted_blocks"],
                prefix_promoted_tokens=cs["promoted_tokens"],
                prefix_saved_bytes=cs["hit_tokens"]
                * self._token_bytes)
            sp = self.prefix_cache.spill
            if sp is not None:
                self.kv_stats.update(
                    prefix_spilled_blocks=sp.stats["spilled_blocks"],
                    prefix_spilled_bytes=sp.stats["spilled_bytes_total"])
        if self.obs.enabled:
            tr = self.obs.trace
            tr.end("queued", rid=req.rid)
            tr.begin("prefill", rid=req.rid, slot=req.slot,
                     blocks=len(req.blocks), prefix_hit=req.prefix_hit)
            self._h_queue_wait.observe(self._step_count - req.submit_step)
        self._on_admit(req)

    # Subclass hooks (speculative engine mirrors these into its proposer).
    def _on_admit(self, req: Request) -> None:
        pass

    def _on_prefill_chunk(self, req: Request, chunk: list,
                          pos0: int) -> None:
        pass

    def _on_retire(self, req: Request) -> None:
        pass

    def _on_preempt(self, req: Request) -> None:
        pass

    def _on_restore(self, req: Request) -> None:
        pass

    def _on_drop(self, req: Request) -> None:
        """A slot-holding request leaves the engine abnormally
        (cancelled / expired / quarantined); ``req.slot`` is still
        valid. Subclasses tear down mirror state here."""

    def run_until_done(self, max_steps: int = 10_000) -> None:
        """Drive steps until every request finishes. Raises ``StallError``
        (carrying per-request diagnostics; with telemetry attached the
        same fields also land as one ``stall`` trace event per stuck
        request) if ``max_steps`` pass with work still pending — a silent
        return here used to mask livelocks and left callers holding
        half-finished requests."""
        for _ in range(max_steps):
            if not self.scheduler.num_unfinished:
                return
            self.step()
        if self.scheduler.num_unfinished:
            diags = self.request_diagnostics()
            self.kv_stats["stalled_requests"] = len(diags)
            if self.obs.enabled:
                for d in diags:
                    self.obs.trace.instant("stall", rid=d["rid"], **{
                        k: v for k, v in d.items() if k != "rid"})
            raise StallError(
                f"{len(diags)} requests unfinished after {max_steps} "
                f"steps", diags)

    def request_diagnostics(self) -> list[dict]:
        """One dict per in-flight request: queue state, slot, blocks
        held, prefill/emit progress, steps since last progress."""
        sched = self.scheduler
        out = []
        for state, reqs in (("waiting", sched.waiting),
                            ("prefilling", sched.prefilling),
                            ("decoding", sched.decoding.values()),
                            ("preempted", sched.preempted)):
            for req in reqs:
                out.append({
                    "rid": req.rid, "state": state, "slot": req.slot,
                    "blocks_held": len(req.blocks),
                    "prefill_pos": req.prefill_pos,
                    "emitted": len(req.output),
                    "steps_since_progress":
                        self._step_count - req.last_progress_step,
                })
        return out

    # ----------------------------------------------- lifecycle control ----

    def _in_flight(self) -> list[Request]:
        sched = self.scheduler
        return (list(sched.waiting) + list(sched.prefilling)
                + list(sched.decoding.values()) + list(sched.preempted))

    def cancel(self, rid: int) -> bool:
        """Cancel an in-flight request wherever it is (waiting,
        prefilling, decoding, preempted), releasing its slot, refcounted
        blocks (trie-shared blocks survive via their remaining
        references), swap snapshot and proposer mirror state. Returns
        False if no such request is in flight."""
        for req in self._in_flight():
            if req.rid == rid:
                return self._terminate(req, "cancelled")
        return False

    def cancel_all(self) -> int:
        """Cancel everything in flight (the serve loop's hard-shutdown
        path); returns how many requests were cancelled."""
        return sum(self._terminate(r, "cancelled")
                   for r in self._in_flight())

    def _expire_deadlines(self) -> None:
        for req in self._in_flight():
            if (req.deadline_steps is not None
                    and self._step_count - req.submit_step
                    > req.deadline_steps):
                self._terminate(req, "expired")

    # Which lifecycle span is open on a request's track, by queue state —
    # terminal paths close it before stamping their terminal instant.
    _STATE_SPANS = {"queued": "queued", "prefilling": "prefill",
                    "decoding": "decode", "preempted": "preempted"}

    def _close_span(self, req: Request) -> None:
        span = self._STATE_SPANS.get(req.state)
        if span is not None:
            self.obs.trace.end(span, rid=req.rid)

    def _terminate(self, req: Request, state: str) -> bool:
        sched = self.scheduler
        slot = req.slot
        preempted = req in sched.preempted
        active = slot is not None and (req in sched.prefilling
                                       or sched.decoding.get(slot) is req)
        if active:
            # mirror teardown needs the slot still valid
            self._on_drop(req)
        if self.obs.enabled and (active or preempted
                                 or req in sched.waiting):
            self._close_span(req)
            self.obs.trace.instant(state, rid=req.rid,
                                   emitted=len(req.output))
        if not sched.drop(req, state):
            return False
        if preempted:
            self.swap.drop(req.rid)
        if active:
            self.caches = self._reset_slot(self.caches, jnp.int32(slot),
                                           self._null_row)
            req.slot = None
        self.kv_stats[state] += 1
        return True

    # ------------------------------------------------ preemption-to-host --

    def preempt(self, rid: int) -> None:
        """Preempt a DECODING request: snapshot its blocks (every pool
        leaf, scale tiles included) to host memory, release them, free
        the slot. The request re-admits — bitwise — once the waiting
        queue has drained (``Scheduler.admit``)."""
        req = next((r for r in self.scheduler.decoding.values()
                    if r.rid == rid), None)
        if req is None:
            raise KeyError(
                f"request {rid} is not decoding; only decoding requests "
                f"hold restorable KV state")
        slot = req.slot
        prof = self.obs.profile
        t0 = time.perf_counter() if prof is not None else 0.0
        out_before = self.swap.stats["host_bytes_total"]
        self.swap.swap_out(rid, self.caches, req.blocks)
        if prof is not None:
            prof.record(
                "swap_out", wall_s=time.perf_counter() - t0,
                host_bytes=self.swap.stats["host_bytes_total"] - out_before)
        self.kv_stats["preempted"] += 1
        self.kv_stats["preempted_blocks"] += len(req.blocks)
        if self.obs.enabled:
            self.obs.trace.end("decode", rid=req.rid)
            self.obs.trace.begin("preempted", rid=req.rid,
                                 blocks=len(req.blocks))
        self._on_preempt(req)
        self.scheduler.preempt(req)
        self.caches = self._reset_slot(self.caches, jnp.int32(slot),
                                       self._null_row)

    def _preempt_for_head(self) -> bool:
        """Pick and preempt one victim to make room for the FIFO head;
        False when the policy yields no eligible victim."""
        head = self.scheduler.waiting[0]
        cands = list(self.scheduler.decoding.values())
        if self.preempt_policy == "priority":
            victim = min(cands, key=lambda r: (r.priority, -r.admit_seq))
            if victim.priority >= head.priority:
                return False
        else:   # "lru": most recently admitted — least completed work
            victim = max(cands, key=lambda r: r.admit_seq)
        self.preempt(victim.rid)
        return True

    def _restore(self, req: Request) -> None:
        """Device-side half of re-admission after preemption: fresh
        blocks are already allocated (IDs need not match the originals —
        content is table-addressed), the host snapshot scatters back,
        and the cached length returns to ``prompt + emitted - 1`` (the
        last emitted token is pending in ``_next_tokens``, exactly the
        decode-step invariant). Decoding resumes bitwise."""
        row = np.full((self.layout.max_blocks,), NULL_BLOCK, np.int32)
        row[:len(req.blocks)] = req.blocks
        self.caches = self._reset_slot(self.caches, jnp.int32(req.slot),
                                       jnp.asarray(row))
        prof = self.obs.profile
        t0 = time.perf_counter() if prof is not None else 0.0
        in_before = self.swap.stats["restored_bytes_total"]
        self.caches = self.swap.swap_in(req.rid, self.caches, req.blocks)
        if prof is not None:
            with obs.span("engine.fetch"):
                jax.block_until_ready(self.caches)
            prof.record(
                "swap_in", wall_s=time.perf_counter() - t0,
                host_bytes=self.swap.stats["restored_bytes_total"]
                - in_before)
        kvlen = req.prefill_pos + len(req.output) - 1
        self.caches = self._set_lens(
            self.caches, jnp.asarray([req.slot], jnp.int32),
            jnp.asarray([kvlen], jnp.int32))
        self._next_tokens[req.slot, 0] = int(req.output[-1])
        self.kv_stats["restored_blocks"] += len(req.blocks)
        if self.obs.enabled:
            self.obs.trace.end("preempted", rid=req.rid)
            self.obs.trace.begin("decode", rid=req.rid,
                                 restored_blocks=len(req.blocks))
        req.last_progress_step = self._step_count
        self.scheduler.start_decoding(req)
        self._on_restore(req)

    # ------------------------------------------------ session spill tier --

    def _promote_gate(self, mode: str) -> float:
        """The restore-vs-reprefill ratio ``PrefixCache.promote`` gates
        on (> 1 promotes). ``"auto"`` prices one block of this engine's
        KV against re-prefilling its tokens on the ECM's modeled
        accelerator — the ratio is token-count-independent (both sides
        are linear in tokens), so one block stands for any chain."""
        if mode == "always":
            return float("inf")
        if mode == "never":
            return 0.0
        from repro.ecm import tpu as ecm_tpu

        n_params = sum(int(p.size)
                       for p in jax.tree_util.tree_leaves(self.params))
        return ecm_tpu.predicted_restore_vs_reprefill(
            self.layout.block_size, self._token_bytes, 2 * n_params)

    def _promote_restore(self, blocks: list[int], snaps: list[dict],
                         *, rid: int | None = None) -> None:
        """Device half of a spill-tier promote: ONE batched scatter of
        the chain's per-block host snapshots into the freshly allocated
        blocks. Runs at match time, before any admission outcome —
        promoted blocks are valid (ordinary, evictable) cache content
        the moment this returns, so a failed admission cannot leave trie
        nodes pointing at garbage."""
        prof = self.obs.profile
        t0 = time.perf_counter() if prof is not None else 0.0
        snap = paged.concat_block_snapshots(snaps)
        self.caches = paged.restore_blocks(self.caches, blocks, snap)
        if prof is not None:
            with obs.span("engine.fetch"):
                jax.block_until_ready(self.caches)
            prof.record(
                "prefix_promote", wall_s=time.perf_counter() - t0,
                host_bytes=sum(int(a.nbytes) for a in snap.values()))

    # -------------------------------------------- faults & quarantine -----

    def _inject_step_faults(self) -> None:
        """Step-granular injection sites: corrupt a decoding victim's KV
        block (NaNs in float pool leaves / scale tiles — the numerics
        guard must catch the fallout) and arm a one-shot allocator
        failure (the admission path must absorb it)."""
        step = self._step_count
        if (self.scheduler.decoding
                and self.injector.fire("kv_corrupt", step)):
            reqs = [self.scheduler.decoding[s]
                    for s in sorted(self.scheduler.decoding)]
            victim = reqs[self.injector.choose("kv_corrupt", step,
                                               len(reqs))]
            alloc = self.scheduler.allocator
            bs = self.layout.block_size
            # prefer a privately held block that already carries cached
            # tokens: its NaNs enter the victim's very next attention
            # read (shared blocks would poison innocent readers)
            priv = [b for b in victim.blocks if alloc.refcount(b) == 1]
            cached = [b for i, b in enumerate(victim.blocks)
                      if alloc.refcount(b) == 1
                      and i * bs < victim.num_cached - 1]
            target = (cached or priv)[:1]
            if target:
                self.caches = paged.poison_blocks(self.caches, target)
        if self.injector.fire("alloc_fail", self._step_count):
            self.scheduler.allocator.fail_next = True

    def _guard_tripped(self, stats: dict, row_reqs) -> list:
        """Evaluate the numerics guard over host-side stats rows;
        returns [(req, reason)] for every tripped row."""
        if self.guard is None:
            return []
        reasons = self.guard.check_rows(stats)
        return [(req, reasons[idx]) for idx, req in row_reqs
                if idx in reasons]

    def _quarantine(self, req: Request, reason: str) -> None:
        """A numerics guard tripped on this slot: scrub the request's
        privately held blocks (NaNs must never ride a recycled block —
        masked attention's exact-zero weights still produce 0 * NaN =
        NaN), release everything, and park the request on
        ``self.quarantined`` for a degraded-path retry
        (``repro.serving.faults.FailoverServer``) instead of letting it
        poison the batch."""
        with obs.span("engine.retire"):
            self.kv_stats["guard_trips"] += 1
            req.error = reason
            if self.obs.enabled:
                self.obs.trace.instant("guard_trip", rid=req.rid,
                                       reason=reason)
                self._close_span(req)
                self.obs.trace.instant("quarantined", rid=req.rid,
                                       emitted=len(req.output))
            self._on_drop(req)
            alloc = self.scheduler.allocator
            scrub = [b for b in req.blocks if alloc.refcount(b) == 1]
            if scrub:
                self.caches = paged.zero_blocks(self.caches, scrub)
            slot = req.slot
            dropped = self.scheduler.drop(req, "quarantined")
            assert dropped, f"quarantine of request {req.rid} not in flight"
            self.caches = self._reset_slot(self.caches, jnp.int32(slot),
                                           self._null_row)
            req.slot = None
            self.quarantined.append(req)

    @property
    def num_active(self) -> int:
        """Requests currently decoding (resident in the batch)."""
        return len(self.scheduler.decoding)

    @property
    def num_unfinished(self) -> int:
        """Everything still owed tokens: waiting + prefilling + decoding."""
        return self.scheduler.num_unfinished

    @property
    def prefix_hit_rate(self) -> float:
        """Fraction of admitted prompt tokens served from the prefix
        cache (0.0 when the cache is off — nothing was ever matched)."""
        tot = self.kv_stats["prefix_prompt_tokens"]
        return self.kv_stats["prefix_hit_tokens"] / tot if tot else 0.0

    # ------------------------------------------------------- telemetry ----

    # Units for the kv_stats counters as they appear in the typed
    # registry / Prometheus exposition.
    _METRIC_UNITS = {
        "paged_bytes": "bytes", "paged_bytes_bf16": "bytes",
        "contiguous_bytes": "bytes", "decode_steps": "steps",
        "prefill_chunks": "chunks", "prefill_tokens": "tokens",
        "prefix_hit_tokens": "tokens", "prefix_prompt_tokens": "tokens",
        "prefix_saved_bytes": "bytes", "prefix_cow_blocks": "blocks",
        "prefix_evicted_blocks": "blocks",
        "prefix_spilled_blocks": "blocks", "prefix_spilled_bytes": "bytes",
        "prefix_promoted_blocks": "blocks",
        "prefix_promoted_tokens": "tokens", "preempted": "requests",
        "preempted_blocks": "blocks", "restored_blocks": "blocks",
        "guard_trips": "trips", "cancelled": "requests",
        "expired": "requests", "alloc_faults": "faults",
        "stalled_requests": "requests", "spec_steps": "steps",
        "spec_slot_steps": "walks", "spec_drafted": "tokens",
        "spec_accepted": "tokens", "spec_emitted": "tokens",
        "proposer_stalls": "stalls", "held_rows": "rows",
    }

    def metrics_registry(self) -> obs.MetricsRegistry:
        """Assemble the full typed registry for this engine, RIGHT NOW:
        every ``kv_stats`` counter mirrored verbatim (the snapshot
        subsumes the legacy dict value-for-value — the single source of
        truth stays the engine's own accounting), swap-pool counters,
        derived-rate gauges, and — when telemetry is attached — the live
        TTFT / queue-wait histograms."""
        reg = obs.MetricsRegistry()
        for key, val in self.kv_stats.items():
            c = reg.counter(key, unit=self._METRIC_UNITS.get(key, ""),
                            help=f"engine kv_stats[{key!r}]")
            c.value = val
        for key in ("swapped_out_blocks", "restored_blocks",
                    "dropped_blocks", "host_bytes_total",
                    "restored_bytes_total"):
            c = reg.counter(
                f"swap_{key}",
                unit="bytes" if "bytes" in key else "blocks",
                help=f"KVSwap stats[{key!r}]")
            c.value = self.swap.stats[key]
        reg.gauge("swap_host_bytes", unit="bytes",
                  help="host bytes currently holding swapped snapshots"
                  ).set(self.swap.stats["host_bytes"])
        reg.gauge("prefix_hit_rate",
                  help="fraction of admitted prompt tokens served from "
                       "the prefix cache").set(self.prefix_hit_rate)
        sp = (self.prefix_cache.spill
              if self.prefix_cache is not None else None)
        if sp is not None:
            reg.gauge("prefix_host_blocks", unit="blocks",
                      help="evicted prefix blocks currently resident in "
                           "the host spill tier").set(len(sp))
            reg.gauge("prefix_host_bytes", unit="bytes",
                      help="host bytes currently holding spilled prefix "
                           "blocks").set(sp.stats["host_bytes"])
        stats = getattr(self, "last_logit_stats", None)
        if stats is not None:
            reg.gauge("round_off_deviation",
                      help="max round_off logit deviation over the last "
                           "decode step (paper's Kahan-vs-naive metric)"
                      ).set(float(np.max(stats["round_off"])))
        if self.obs.enabled:
            reg.merge(self.obs.metrics)
        return reg

    def metrics_snapshot(self) -> dict:
        """Plain dict of every metric — contains every ``kv_stats`` key
        with the identical value plus derived gauges and (with telemetry)
        histogram summaries. This is the JSON ``--metrics`` exports and
        the dict the launcher's final summary line renders from."""
        return self.metrics_registry().snapshot()

    def metrics_prometheus(self) -> str:
        """Prometheus text exposition of the same registry."""
        return self.metrics_registry().to_prometheus()

    # ------------------------------------------------------- internals ----

    @staticmethod
    def _sample_key(req: Request) -> jax.Array:
        """The request's private stream, keyed on (seed, emit index) only —
        invariant to batch composition and admission timing."""
        return jax.random.fold_in(jax.random.key(req.seed), len(req.output))

    def _choose_token(self, req: Request, row: jax.Array) -> int:
        """Greedy argmax unless the request opted into sampling. ``row`` is
        the device-side logit row."""
        if req.temperature <= 0.0:
            tok = jnp.argmax(row)
        else:
            tok = _sample_rows(row[None],
                               jnp.asarray([req.temperature], jnp.float32),
                               self._sample_key(req)[None], req.top_k)[0]
        with obs.span("engine.fetch"):
            return int(tok)

    def _emit_first_token(self, req: Request, logits: jax.Array) -> None:
        """Final prefill chunk's logits yield the request's first token."""
        tok = self._choose_token(req, logits[0])
        prof = self.obs.profile
        t0 = time.perf_counter() if prof is not None else 0.0
        row = logits.reshape(1, -1)
        tok_arr = jnp.asarray([tok], jnp.int32)
        stats = _logit_stats(row, tok_arr)
        with obs.span("engine.fetch"):
            host_stats = {k: np.asarray(v) for k, v in stats.items()}
        if prof is not None:
            # a named ops.* dispatch: the first-token stats pass is the
            # one _logit_stats launch the fused decode step doesn't fold
            prof.record_call(
                "ops.logit_stats", _logit_stats, (row, tok_arr),
                wall_s=time.perf_counter() - t0,
                host_bytes=sum(int(v.nbytes) for v in host_stats.values()),
                static_shapes=True)
        tripped = self._guard_tripped(host_stats, [(0, req)])
        if self.obs.enabled:
            # the decode span opens either way; the quarantine path
            # closes it again via _close_span, keeping B/E balanced
            self.obs.trace.end("prefill", rid=req.rid)
            self.obs.trace.begin("decode", rid=req.rid)
        if tripped:
            # not yet registered as decoding — route through the shared
            # quarantine path so slot + blocks release uniformly
            self.scheduler.start_decoding(req)
            self._quarantine(req, tripped[0][1])
            return
        if self.obs.enabled:
            self._h_ttft.observe(self._step_count - req.submit_step)
        req.output.append(tok)
        req.logprobs.append(float(host_stats["logprob"][0]))
        req.last_progress_step = self._step_count
        self._next_tokens[req.slot, 0] = tok
        if self._finished(req, tok):
            self._retire(req)
        else:
            self.scheduler.start_decoding(req)

    def _decode_step(self) -> None:
        if self.obs.enabled:
            self.obs.trace.instant("decode_step",
                                   batch=len(self.scheduler.decoding))
        prof = self.obs.profile
        t0 = time.perf_counter() if prof is not None else 0.0
        tok_in = jnp.asarray(self._next_tokens)
        feed = (tok_in, self._live_mask())
        rows, packed_dev, self.caches = self._decode(
            self.params, feed, self.caches)
        injected = (self.injector is not None
                    and self.injector.fire("logit_nan", self._step_count))
        if injected:
            # fault injection: NaN one decoding victim's whole logit row
            # — the guard's nonfinite sentinel must quarantine it
            slots_sorted = sorted(self.scheduler.decoding)
            victim = slots_sorted[self.injector.choose(
                "logit_nan", self._step_count, len(slots_sorted))]
            rows = rows.at[victim].set(jnp.nan)
        sampled = {slot: req for slot, req in self.scheduler.decoding.items()
                   if req.temperature > 0.0}
        if sampled:
            # override the batched greedy choice for slots that asked for
            # temperature/top-k sampling: one vmapped launch per distinct
            # top_k (usually one total) — draws stay device-side, only the
            # chosen indices cross
            greedy = _greedy_tokens(rows)
            with obs.span("engine.fetch"):
                toks = np.asarray(greedy).copy()
            by_k: dict[int, list] = {}
            for slot, req in sampled.items():
                by_k.setdefault(req.top_k, []).append((slot, req))
            for top_k, items in by_k.items():
                slots = [s for s, _ in items]
                ts = time.perf_counter() if prof is not None else 0.0
                sample_args = (
                    rows[jnp.asarray(slots, jnp.int32)],
                    jnp.asarray([r.temperature for _, r in items],
                                jnp.float32),
                    jnp.stack([self._sample_key(r) for _, r in items]),
                    top_k)
                draws = _sample_rows(*sample_args)
                with obs.span("engine.fetch"):
                    toks[slots] = np.asarray(draws)
                if prof is not None:
                    prof.record_call(
                        "ops.sample_rows", _sample_rows, sample_args,
                        wall_s=time.perf_counter() - ts,
                        host_bytes=draws.nbytes)
            tokens_dev = jnp.asarray(toks, jnp.int32)
            # fused logprob/metric pass over the final token choices; only
            # (B,)-sized arrays ever reach the host
            stats = _logit_stats(rows, tokens_dev)
            with obs.span("engine.fetch"):
                tokens = np.asarray(tokens_dev)
                self.last_logit_stats = {k: np.asarray(v)
                                         for k, v in stats.items()}
        elif injected:
            # choice + stats must see the poisoned rows, not the fused
            # pre-injection packing
            tokens_dev = _greedy_tokens(rows)
            stats = _logit_stats(rows, tokens_dev)
            with obs.span("engine.fetch"):
                tokens = np.asarray(tokens_dev)
                self.last_logit_stats = {k: np.asarray(v)
                                         for k, v in stats.items()}
        else:
            # all-greedy: the fused decode launch already packed tokens +
            # stats — ONE host transfer covers the step
            with obs.span("engine.fetch"):
                packed = np.asarray(packed_dev)
            tokens = packed[0].astype(np.int32)
            self.last_logit_stats = {k: packed[i + 1]
                                     for i, k in enumerate(_STAT_KEYS)}
        logprobs = self.last_logit_stats["logprob"]
        self._account_decode()
        tripped = self._guard_tripped(
            self.last_logit_stats,
            [(slot, req) for slot, req in self.scheduler.decoding.items()])
        skip = {req.rid for req, _ in tripped}
        retired = []
        for slot, req in self.scheduler.decoding.items():
            if req.rid in skip:
                continue
            tok = int(tokens[slot])
            req.output.append(tok)
            req.logprobs.append(float(logprobs[slot]))
            req.last_progress_step = self._step_count
            self._next_tokens[slot, 0] = tok
            if self._finished(req, tok):
                retired.append(req)
        for req, reason in tripped:
            self._quarantine(req, reason)
        for req in retired:
            self._retire(req)
        if prof is not None:
            # the phase wall covers the whole step (launch + the one
            # host transfer + per-request bookkeeping — whatever the
            # launch doesn't explain lands in "unattributed"); the HLO
            # cost is the fused decode launch's, cached once since the
            # frame shapes never change (static_shapes)
            prof.record_call(
                "decode_step", self._decode,
                (self.params, feed, self.caches),
                wall_s=time.perf_counter() - t0,
                host_bytes=tok_in.nbytes + tokens.nbytes
                + sum(int(v.nbytes) for v in self.last_logit_stats.values()),
                static_shapes=True)

    def _live_mask(self) -> jax.Array:
        """[max_slots] bool, true for the decoding slots: the rows the
        decode launch advances. The device copy is rebuilt only when the
        decoding set changed since the last step."""
        slots = frozenset(self.scheduler.decoding)
        if slots != self._live_slots:
            mask = np.zeros((self.max_slots,), bool)
            mask[list(slots)] = True
            self._live, self._live_slots = jnp.asarray(mask), slots
        return self._live

    def _finished(self, req: Request, tok: int) -> bool:
        return (len(req.output) >= req.max_new_tokens
                or (req.eos_id is not None and tok == req.eos_id))

    def _retire(self, req: Request) -> None:
        with obs.span("engine.retire"):
            slot = req.slot
            if self.obs.enabled:
                self.obs.trace.end("decode", rid=req.rid)
                self.obs.trace.instant("retired", rid=req.rid,
                                       emitted=len(req.output))
            self._on_retire(req)
            self.scheduler.retire(req)
            # Point the slot's tables back at the null block so the next
            # batched steps' stray writes can't touch re-allocated blocks.
            self.caches = self._reset_slot(self.caches, jnp.int32(slot),
                                           self._null_row)

    # ------------------------------------------------------- accounting ---

    def _account_decode(self) -> None:
        bs = self.layout.block_size
        touched = sum(paged.cdiv(r.num_cached + 1, bs) * bs
                      for r in self.scheduler.decoding.values())
        self.kv_stats["paged_bytes"] += touched * self._token_bytes
        self.kv_stats["paged_bytes_bf16"] += touched * self._token_bytes_bf16
        self.kv_stats["contiguous_bytes"] += (len(self.scheduler.decoding)
                                              * self.layout.max_context
                                              * self._token_bytes)
        self.kv_stats["decode_steps"] += 1
        # rows the decode launch held at their length, not advanced
        self.kv_stats["held_rows"] += (self.max_slots
                                       - len(self.scheduler.decoding))

    def _account_prefill(self, cached: int, *, first: bool) -> None:
        bs = self.layout.block_size
        touched = paged.cdiv(cached, bs) * bs
        self.kv_stats["paged_bytes"] += touched * self._token_bytes
        self.kv_stats["paged_bytes_bf16"] += touched * self._token_bytes_bf16
        if first:
            # contiguous baseline: batch-1 prefill wrote a full max_context
            # row (zero padding included) ONCE per request
            self.kv_stats["contiguous_bytes"] += (self.layout.max_context
                                                  * self._token_bytes)
        self.kv_stats["prefill_chunks"] += 1


class SpecDecodeEngine(DecodeEngine):
    """Speculative continuous-batching engine: draft → verify → accept.

    Each engine step still admits + runs one prefill chunk (the proposer
    mirrors both through hooks), but the batched decode step is replaced by
    a draft/verify cycle: the proposer guesses up to ``spec_k`` tokens per
    decoding slot, ONE fixed-shape ``verify_fn`` launch scores every slot's
    window against the paged KV (quantized pools included), and exact
    accept/reject emits between 1 and k+1 tokens per slot per step. The
    expected emitted length per KV-pool walk is the speedup — the walk is
    the decode path's dominant traffic (``repro.ecm.tpu
    .predicted_spec_speedup`` is the analytic forecast).

    Rollback of a rejected suffix is pure bookkeeping: the slot's ``len``
    drops to the accepted prefix (``paged.set_lens``), blocks stay
    allocated, and scale pools ride the same tables — stale rows past
    ``len`` are masked by every reader and overwritten by the next append.

    Restricted to paged-KV attention families (dense/moe/vlm): recurrent
    SSM state cannot be rolled back by a length decrement. Greedy requests
    emit the identical token stream to ``DecodeEngine``; sampled requests
    stay keyed on (seed, emit index) — reproducible and batch-invariant —
    with the emitted marginal exactly the target distribution.
    """

    _DECODE_SPAN = "engine.verify_step"

    def __init__(self, cfg: ModelConfig, params, *, proposer,
                 spec_k: int = 4, **kw):
        if cfg.family not in ("dense", "moe", "vlm"):
            raise ValueError(
                f"speculative decoding needs a rollback-able paged KV "
                f"cache; family {cfg.family!r} carries recurrent state")
        if spec_k < 1:
            raise ValueError(f"spec_k must be >= 1, got {spec_k}")
        super().__init__(cfg, params, **kw)
        self.proposer = proposer
        self.spec_k = int(spec_k)
        self._verify = jax.jit(api.verify_fn(cfg))
        self.kv_stats.update({"spec_steps": 0, "spec_slot_steps": 0,
                              "spec_drafted": 0, "spec_accepted": 0,
                              "spec_emitted": 0, "proposer_stalls": 0})
        proposer.attach(self)

    # proposer mirrors admission, prompt caching and retirement ----------
    def _on_admit(self, req: Request) -> None:
        self.proposer.on_admit(req)

    def _on_prefill_chunk(self, req: Request, chunk: list,
                          pos0: int) -> None:
        self.proposer.on_prefill_chunk(req, chunk, pos0)

    def _on_retire(self, req: Request) -> None:
        self.proposer.on_retire(req)

    def _on_preempt(self, req: Request) -> None:
        self.proposer.on_preempt(req)

    def _on_restore(self, req: Request) -> None:
        self.proposer.on_restore(req)

    def _on_drop(self, req: Request) -> None:
        # cancellation/expiry/quarantine: the mirror slot resets exactly
        # like retirement — the draft cache holds no refcounted blocks
        self.proposer.on_retire(req)

    # ------------------------------------------------------- spec step ----

    def _effective_k(self, req: Request) -> int:
        """Drafts actually worth proposing for this request now: the
        engine window, the request knob, the remaining token budget and
        the slot's allocated blocks all cap it. k == 0 degenerates to a
        plain (verify-path) decode step for that slot."""
        k = self.spec_k if req.spec_k is None else min(req.spec_k,
                                                       self.spec_k)
        k = min(k, req.max_new_tokens - len(req.output) - 1)
        cached = req.prefill_pos + len(req.output) - 1
        capacity = len(req.blocks) * self.layout.block_size
        return max(0, min(k, capacity - cached - 1))

    def _decode_step(self) -> None:
        from repro.spec import sampler as spec_sampler
        from repro.spec.verify import pack_windows

        prof = self.obs.profile
        t0 = time.perf_counter() if prof is not None else 0.0
        decoding = [self.scheduler.decoding[s]
                    for s in sorted(self.scheduler.decoding)]
        ks = [self._effective_k(r) for r in decoding]
        if self.obs.enabled:
            self.obs.trace.instant("verify_step", batch=len(decoding),
                                   drafted=sum(ks))
        stalled = (self.injector is not None
                   and self.injector.fire("proposer_stall",
                                          self._step_count))
        if not stalled:
            try:
                with obs.span("engine.propose"):
                    drafts, qdists = self.proposer.propose(decoding, ks)
            except ProposerStallError:
                stalled = True
        if stalled:
            # degrade, don't crash: zero drafts turn this step into the
            # plain verify-path decode (k == 0 for every slot) — one
            # token per slot, exact, just unaccelerated
            drafts = [[] for _ in decoding]
            qdists = [None] * len(decoding)
            ks = [0] * len(decoding)
            self.kv_stats["proposer_stalls"] += 1

        window = self.spec_k + 1
        tokens, slots, pos0s = pack_windows(decoding, ks, drafts,
                                            self.max_slots, window)
        logits, self.caches = self._verify(
            self.params, jnp.asarray(tokens), self.caches,
            jnp.asarray(slots), jnp.asarray(pos0s))
        if (self.injector is not None
                and self.injector.fire("logit_nan", self._step_count)):
            victim = self.injector.choose("logit_nan", self._step_count,
                                          len(decoding))
            logits = logits.at[victim].set(jnp.nan)
        argmax_dev = jnp.argmax(logits, axis=-1)
        with obs.span("engine.fetch"):
            argmax = np.asarray(argmax_dev)                    # [B, C]
        # Greedy batches keep the host-transfer discipline (only the
        # [B, C] argmax crosses). Exact accept/residual math for SAMPLED
        # requests currently pulls the full [B, C, V] rows — fine at this
        # repo's CPU-test vocab sizes, but a device-side rejection sampler
        # (the _sample_rows treatment applied to accept/residual draws)
        # is what a large-vocab deployment needs; see ROADMAP.
        rows = None
        if any(r.temperature > 0.0 for r in decoding):
            head = logits[:len(decoding)]
            with obs.span("engine.fetch"):
                rows = np.asarray(head, np.float32)

        emitted_all: list[list[int]] = []
        accepted: list[int] = []
        new_lens: list[int] = []
        for i, req in enumerate(decoding):
            if req.temperature <= 0.0:
                acc, emitted = spec_sampler.greedy_verify(
                    argmax[i], drafts[i][:ks[i]])
            else:
                acc, emitted = spec_sampler.rejection_sample(
                    rows[i], drafts[i][:ks[i]], qdists[i],
                    req.temperature, req.top_k, req.seed,
                    len(req.output))
            emitted_all.append(emitted)
            accepted.append(acc)
            new_lens.append(int(pos0s[i]) + 1 + acc)

        # one fused stats launch prices every emitted token's logprob
        chosen = np.zeros(tokens.shape, np.int32)
        for i, emitted in enumerate(emitted_all):
            chosen[i, :len(emitted)] = emitted
        stats = _logit_stats(logits.reshape(-1, logits.shape[-1]),
                             jnp.asarray(chosen.reshape(-1), jnp.int32))
        with obs.span("engine.fetch"):
            self.last_logit_stats = {k: np.asarray(v).reshape(tokens.shape)
                                     for k, v in stats.items()}
        logprobs = self.last_logit_stats["logprob"]

        # rollback: rejected suffixes disappear by length bookkeeping only
        lens_pad = np.full((self.max_slots,), new_lens[0], np.int32)
        lens_pad[:len(decoding)] = new_lens
        self.caches = self._set_lens(self.caches, jnp.asarray(slots),
                                     jnp.asarray(lens_pad))

        self._account_spec(pos0s[:len(decoding)], ks, emitted_all, accepted)

        tripped = self._guard_tripped(
            self.last_logit_stats,
            [(i, req) for i, req in enumerate(decoding)])
        skip = {req.rid for req, _ in tripped}
        retired, alive, alive_lens = [], [], []
        for i, req in enumerate(decoding):
            if req.rid in skip:
                continue
            done = False
            for j, tok in enumerate(emitted_all[i]):
                req.output.append(int(tok))
                req.logprobs.append(float(logprobs[i, j]))
                if self._finished(req, int(tok)):
                    done = True
                    break
            req.last_progress_step = self._step_count
            self._next_tokens[req.slot, 0] = req.output[-1]
            if done:
                retired.append(req)
            else:
                alive.append(req)
                alive_lens.append(new_lens[i])
        self.proposer.sync(alive, alive_lens)
        for req, reason in tripped:
            self._quarantine(req, reason)
        for req in retired:
            self._retire(req)
        if prof is not None:
            # the verify frame is fixed-shape ([max_slots, window]), so
            # the HLO cost resolves once; sampled requests' full-row
            # pull shows up as extra host bytes
            prof.record_call(
                "verify_step", self._verify,
                (self.params, jnp.asarray(tokens), self.caches,
                 jnp.asarray(slots), jnp.asarray(pos0s)),
                wall_s=time.perf_counter() - t0,
                host_bytes=tokens.nbytes + argmax.nbytes
                + (rows.nbytes if rows is not None else 0)
                + sum(int(v.nbytes)
                      for v in self.last_logit_stats.values()),
                static_shapes=True)

    def _account_spec(self, pos0s, ks, emitted_all, accepted) -> None:
        bs = self.layout.block_size
        window = self.spec_k + 1
        # one KV-pool walk per slot covers the whole window (the spec win);
        # the contiguous baseline still pays a max_context row PER TOKEN
        touched = sum(paged.cdiv(int(p) + window, bs) * bs for p in pos0s)
        n_emitted = sum(len(e) for e in emitted_all)
        self.kv_stats["paged_bytes"] += touched * self._token_bytes
        self.kv_stats["paged_bytes_bf16"] += touched * self._token_bytes_bf16
        self.kv_stats["contiguous_bytes"] += (n_emitted
                                              * self.layout.max_context
                                              * self._token_bytes)
        self.kv_stats["decode_steps"] += 1
        self.kv_stats["spec_steps"] += 1
        self.kv_stats["spec_slot_steps"] += len(pos0s)
        self.kv_stats["spec_drafted"] += sum(ks)
        self.kv_stats["spec_accepted"] += sum(accepted)
        self.kv_stats["spec_emitted"] += n_emitted

    @property
    def acceptance_rate(self) -> float:
        """Fraction of drafted tokens the target accepted so far."""
        drafted = self.kv_stats["spec_drafted"]
        return self.kv_stats["spec_accepted"] / drafted if drafted else 0.0

    @property
    def mean_accepted_length(self) -> float:
        """Tokens emitted per per-slot verify walk (the amortization
        factor the ECM speedup model forecasts)."""
        walks = self.kv_stats["spec_slot_steps"]
        return self.kv_stats["spec_emitted"] / walks if walks else 0.0

    def metrics_registry(self) -> obs.MetricsRegistry:
        reg = super().metrics_registry()
        reg.gauge("acceptance_rate",
                  help="fraction of drafted tokens the target accepted"
                  ).set(self.acceptance_rate)
        reg.gauge("mean_accepted_length", unit="tokens",
                  help="tokens emitted per per-slot verify walk (the "
                       "measured side of predicted_spec_speedup)"
                  ).set(self.mean_accepted_length)
        return reg
