"""Typed serving failures, numerics guards and deterministic fault
injection.

The paper's thesis is that numerical robustness (compensated summation)
costs almost nothing when engineered into the hot loop; this module is
the OPERATIONAL half of that story. Three pieces:

``AllocatorError`` / ``AdmissionError`` / ``StallError``
    Typed, recoverable exceptions replacing the allocator/scheduler
    assertions — the engine can catch an allocation failure and make the
    head-of-line request wait instead of crashing the batch, and a
    stalled ``run_until_done`` surfaces per-request diagnostics instead
    of returning silently.

``NumericsGuard``
    Per-step health checks on the fused ``_logit_stats`` pass (the
    (B,)-sized arrays that already cross to the host every step — the
    guard adds no transfers). Two detectors: a NaN/Inf sentinel on the
    row statistics, and a round-off check in the spirit of Dukhan &
    Vondele (arXiv:1603.00491) — the compensated reduction engine's sum
    and a naive float32 sum are both computed in the same fused pass, and
    their deviation IS the accumulated round-off of the naive stream.
    Corrupted or catastrophically cancelling logit rows blow that
    deviation up many orders of magnitude above the ~1e-7 relative error
    of a healthy row. A tripped slot is quarantined (blocks scrubbed and
    released), never poisoning the rest of the batch.

``FaultInjector``
    Keyed, replayable fault injection. Sites are keyed exactly like the
    engine's sampling streams (``jax.random.fold_in`` chains over (seed,
    site, step)), so a failing run replays bit-for-bit from its seed: the
    injector can NaN a logit row, corrupt a KV block (via
    ``paged.poison_blocks``), fail an allocator call, or stall a spec
    proposer. ``FailoverServer`` closes the loop: requests quarantined by
    a guard are retried on a degraded engine (bf16 pools, speculation
    off) instead of being dropped.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import numpy as np

from repro import obs


class ServingError(RuntimeError):
    """Base class for recoverable serving-stack failures."""


class AllocatorError(ServingError):
    """Block-pool misuse or exhaustion: alloc beyond the free list,
    double free, or retain of a free block. Subclasses RuntimeError so
    pre-existing ``pytest.raises(RuntimeError)`` exhaustion contracts
    still hold."""


class AdmissionError(ServingError, ValueError):
    """A request that can NEVER be admitted (context overflow, pool
    oversubmit, bad deadline) — rejected at submission, not livelocked
    at admission. Subclasses ValueError for back-compat with callers
    that treated submission failures as value errors."""


class SwapMissError(ServingError, KeyError):
    """A host-tier lookup (``KVSwap.swap_in`` / ``KVSwap.drop`` /
    ``PrefixSpill.take``) named a request id or trie path with no
    snapshot. Both directions raise — symmetrically —
    because a silent no-op on either path can mask a LOST snapshot: a
    drop that misses hides leaked host bytes, a swap-in that misses
    would resume a request with uninitialized KV. Subclasses KeyError so
    pre-existing ``pytest.raises(KeyError)`` restore contracts hold."""


class ProposerStallError(ServingError):
    """A speculative-decoding proposer failed to produce drafts this
    step. The spec engine degrades the step to the plain verify-path
    decode (k == 0 for every slot) instead of crashing."""


class StallError(ServingError):
    """``run_until_done`` exhausted ``max_steps`` with unfinished
    requests. Carries per-request diagnostics (state, blocks held, steps
    since last progress); with telemetry attached the engine also emits
    the same fields as one ``stall`` trace event per stuck request."""

    def __init__(self, msg: str, diagnostics: list[dict]):
        super().__init__(msg)
        self.diagnostics = diagnostics


@dataclass
class NumericsGuard:
    """Config for the per-step logit health checks (see module doc).

    ``round_off_threshold`` is the trip point for the relative deviation
    between the compensated and naive logit-row sums: healthy float32
    rows at serving vocab sizes sit around 1e-7, catastrophic
    cancellation or corrupted values push it many orders higher. ``None``
    disables that detector; ``check_nonfinite=False`` disables the
    NaN/Inf sentinel."""

    check_nonfinite: bool = True
    round_off_threshold: float | None = 1e-2

    def check_row(self, stats: dict, idx: int) -> str | None:
        """Reason string if row ``idx`` of a host-side stats dict trips a
        detector, else None. Rows may be (B,) scalars or (B, C) windows
        (the spec engine's verify frame) — any bad column trips."""
        if self.check_nonfinite:
            for key in ("max", "logsumexp", "rms"):
                if not np.all(np.isfinite(np.asarray(stats[key])[idx])):
                    return f"nonfinite {key}"
        if self.round_off_threshold is not None and "round_off" in stats:
            dev = np.max(np.asarray(stats["round_off"])[idx])
            if not np.isfinite(dev) or dev > self.round_off_threshold:
                return f"round_off {dev:.3g}"
        return None

    def check_rows(self, stats: dict) -> dict[int, str]:
        """``check_row`` vectorized over every row at once: {idx: reason}
        for tripped rows only. The engine calls this each decode step, so
        the healthy case must cost one numpy pass over (B,)-sized stats —
        not a per-(slot, key) reduction loop. Reason priority matches
        ``check_row`` (first detector to trip names the reason)."""
        reasons: dict[int, str] = {}
        if self.check_nonfinite:
            for key in ("max", "logsumexp", "rms"):
                a = np.asarray(stats[key])
                finite = np.isfinite(a).reshape(a.shape[0], -1).all(axis=1)
                for i in np.nonzero(~finite)[0]:
                    reasons.setdefault(int(i), f"nonfinite {key}")
        if self.round_off_threshold is not None and "round_off" in stats:
            dev = np.asarray(stats["round_off"])
            dev = dev.reshape(dev.shape[0], -1).max(axis=1)
            bad = ~np.isfinite(dev) | (dev > self.round_off_threshold)
            for i in np.nonzero(bad)[0]:
                reasons.setdefault(int(i), f"round_off {dev[i]:.3g}")
        return reasons


@dataclass
class FaultSpec:
    """One armed fault. ``site`` is an injection point (see
    ``FaultInjector.SITES``); firing policy is, in priority order:
    ``step`` (fire exactly when the engine step counter hits it),
    ``rate`` (a keyed per-step Bernoulli draw), or — with neither — fire
    at the first step where the site is reachable, once."""

    site: str
    step: int | None = None
    rate: float = 0.0
    fired: int = 0


class FaultInjector:
    """Deterministic, replayable fault injection for the serving engine.

    Keyed like ``DecodeEngine._sample_key``: every stochastic decision
    (rate draws, victim choices) folds (site, step) into
    ``jax.random.key(seed)``, so two runs with the same seed and workload
    inject identical faults at identical steps — ``self.log`` records
    (step, site, detail) for replay assertions."""

    SITES = ("kv_corrupt", "logit_nan", "alloc_fail", "proposer_stall")

    def __init__(self, seed: int = 0, faults: list[FaultSpec] | None = None):
        self.seed = seed
        self.faults = list(faults or [])
        for f in self.faults:
            if f.site not in self.SITES:
                raise ValueError(f"unknown fault site {f.site!r}; "
                                 f"expected one of {self.SITES}")
        self.log: list[tuple[int, str, dict]] = []
        # shared telemetry handle (set by the owning engine); firings
        # stamp their OWN step — the injector may be consulted before
        # the engine advances the shared trace clock
        self.obs = obs.NULL

    def _key(self, site: str, step: int) -> jax.Array:
        key = jax.random.key(self.seed)
        key = jax.random.fold_in(key, self.SITES.index(site))
        return jax.random.fold_in(key, step)

    def fire(self, site: str, step: int) -> bool:
        """Whether ``site`` fires at engine step ``step``. Call exactly
        once per (site, step) and only when the site is reachable (e.g.
        kv_corrupt needs a decoding victim) — one-shot specs consume
        their charge on the first reachable step."""
        for f in self.faults:
            if f.site != site:
                continue
            if f.step is not None:
                if f.step != step:
                    continue
            elif f.rate > 0.0:
                u = jax.random.uniform(self._key(site, step))
                with obs.span("engine.fetch"):
                    draw = float(u)
                if draw >= f.rate:
                    continue
            elif f.fired:
                continue
            f.fired += 1
            self.log.append((step, site, {}))
            if self.obs.enabled:
                self.obs.trace.instant("fault_injected", step=step,
                                       site=site)
            return True
        return False

    def choose(self, site: str, step: int, n: int) -> int:
        """Keyed victim index in [0, n) — deterministic per (seed, site,
        step), recorded in the log entry for replay checks."""
        pick_dev = jax.random.randint(
            jax.random.fold_in(self._key(site, step), 1), (), 0, n)
        with obs.span("engine.fetch"):
            pick = int(pick_dev)
        if self.log and self.log[-1][:2] == (step, site):
            self.log[-1][2]["choice"] = pick
        return pick


class FailoverServer:
    """Primary engine + lazily built degraded engine.

    Requests the primary engine quarantines (numerics-guard trips — see
    ``DecodeEngine.quarantined``) are reset and resubmitted to a degraded
    engine: by default a plain ``DecodeEngine`` over bf16 pools with
    speculation off — the widest-precision, fewest-moving-parts path. A
    request that trips the guard THERE too is reported in ``failed``
    rather than retried forever."""

    def __init__(self, primary, degraded_factory=None):
        self.primary = primary
        self._factory = degraded_factory or (
            lambda: degraded_engine(primary))
        self.degraded = None
        self.failed: list = []
        self.retried: list = []

    def submit(self, req) -> None:
        self.primary.submit(req)

    def _sweep(self) -> None:
        tele = self.primary.obs
        for req in self._drain(self.primary):
            req.reset_for_retry()
            if self.degraded is None:
                self.degraded = self._factory()
            self.retried.append(req)
            if tele.enabled:
                tele.trace.instant("failover_retry", rid=req.rid)
            self.degraded.submit(req)
        if self.degraded is not None:
            for req in self._drain(self.degraded):
                req.state = "failed"
                if tele.enabled:
                    tele.trace.instant("failover_failed", rid=req.rid)
                self.failed.append(req)

    @staticmethod
    def _drain(engine) -> list:
        out, engine.quarantined = engine.quarantined, []
        return out

    def step(self) -> None:
        if self.primary.num_unfinished:
            self.primary.step()
        self._sweep()
        if self.degraded is not None and self.degraded.num_unfinished:
            self.degraded.step()

    @property
    def num_unfinished(self) -> int:
        n = self.primary.num_unfinished + len(self.primary.quarantined)
        if self.degraded is not None:
            n += self.degraded.num_unfinished + len(
                self.degraded.quarantined)
        return n

    def run_until_done(self, max_steps: int = 10_000) -> None:
        for _ in range(max_steps):
            if not self.num_unfinished:
                return
            self.step()
        self._sweep()
        if self.num_unfinished:
            diags = self.primary.request_diagnostics()
            if self.degraded is not None:
                diags += self.degraded.request_diagnostics()
            raise StallError(
                f"failover server: {self.num_unfinished} requests "
                f"unfinished after {max_steps} steps", diags)


def degraded_engine(primary):
    """The default degraded tier for ``FailoverServer``: a plain
    ``DecodeEngine`` (no speculation) over bf16 pools with the same
    geometry as ``primary``. Guards stay on; fault injection does not
    follow the request to the degraded tier."""
    from repro.serving.engine import DecodeEngine

    cfg = primary.cfg.with_(kv_dtype="bf16")
    return DecodeEngine(
        cfg, primary.params, max_slots=primary.max_slots,
        max_context=primary.layout.max_context,
        block_size=primary.layout.block_size,
        prefill_chunk=primary.scheduler.prefill_chunk,
        guard=primary.guard,
        telemetry=primary.obs if primary.obs.enabled else None)
