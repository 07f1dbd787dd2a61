"""Chip smoke test: serve qwen1.5-0.5b at its published widths on one TPU.

    python chip_smoke.py

Drives the serving main path (``DecodeEngine``: chunked prefill, the
paged-attention superkernel, the fused logit-stats pass) with random
weights from a seed, all in this one process on the first TPU device. It
starts no child process. Phases:

  a  device check: the first JAX device must be a TPU (no CPU fallback)
  b  DecodeEngine, bf16 pools: 8 requests, prompts 16..1008 tokens,
     32 new tokens each; every request finishes, no guard trips, finite
     logprobs
  c  the same with int8 pools (the superkernel's quantized branch)
  d  SpecDecodeEngine with the n-gram proposer (verify walk at W = k + 1)
  e  the compiled decode, verify and logit-stats programs hold the Pallas
     kernels as ``tpu_custom_call``s, so no interpret mode and no gather
     path ran on the chip; the compiled decode and prefill-chunk programs
     (bf16 and int8 pools) copy no K/V payload pool, a layer's or the
     stack, into a non-default layout: the pools are read and written as
     stored (int8's scale tiles still are, and are counted)
  f  reference: on the same caches, the kernel path against the
     gather + flash formulation, for the first decode step and a verify
     window; ``_logit_stats`` against ``jax.nn.logsumexp`` and jnp
     reductions

Lines before the last are smoke numbers (compile and run seconds, tokens,
peak device memory), not benchmark results. A failed phase raises, so the
script exits non-zero and prints no result line. The last line is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}``.
"""

from __future__ import annotations

import json
import re
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "qwen1.5-0.5b"
SEED = 0
SLOTS, MAX_CONTEXT, BLOCK, CHUNK = 8, 2048, 16, 32
# Every prompt is 16 mod 32 tokens long, so chunked prefill compiles just
# two chunk shapes (32 and the 16-token tail).
PROMPT_LENS = (16, 144, 272, 400, 528, 656, 784, 1008)
SPEC_PROMPT_LENS = (48, 112, 176, 240)
MAX_NEW = 32
SPEC_K = 4
# (f) tolerances. Kernel vs gather + flash: both read the same bf16 K/V
# and bf16 activations; they differ in where attention rounds (the
# kernel's p.V runs in f32, flash casts p to bf16, u = 2^-9) and in
# summation order. With random weights those O(u) differences grow with
# depth: a CPU run with XLA's excess precision off (so flash rounds p as
# the TPU does) measured the worst row's ||d|| / ||ref|| at 5.7e-3,
# 2.6e-2, 3.3e-2 for decode and 1.7e-2, 5.0e-2, 6.5e-2 for the verify
# window at 2, 6 and 12 layers: about sqrt(depth), so ~0.05 and ~0.09 at
# 24. On a TPU v5e the 24-layer model measured 0.097-0.111 for decode and
# 0.078-0.085 for the window. LOGIT_RTOL leaves over 2x headroom over
# that and sits 4x below the ~1 of an unrelated result (a wrong head,
# block or mask).
LOGIT_RTOL = 0.25
# _logit_stats vs XLA's f32 reductions: XLA's tree sum over V = 151936
# elements errs by <= ~log2(V) * 2^-24 * sum|l| ~ 1e-6 * sum|l|, the
# compensated engine by far less; 1e-5 leaves 10x headroom. max is exact.
STAT_RTOL = 1e-5


def log(msg: str) -> None:
    print(msg, flush=True)


def peak_gib(dev) -> str:
    """Device memory now in use and the process's high-water mark."""
    stats = dev.memory_stats() or {}
    gib = lambda k: ("n/a" if stats.get(k) is None
                     else f"{stats[k] / 2**30:.2f} GiB")
    return f"{gib('peak_bytes_in_use')} (in use {gib('bytes_in_use')})"


def make_requests(cfg, lens, rng):
    from repro.serving.engine import Request
    return [Request(rid=i,
                    prompt=rng.integers(0, cfg.vocab_size, n).tolist(),
                    max_new_tokens=MAX_NEW)
            for i, n in enumerate(lens)]


def make_repetitive_requests(cfg, lens, rng):
    """Prompts that repeat a short motif, so the n-gram proposer drafts."""
    from repro.serving.engine import Request
    reqs = []
    for i, n in enumerate(lens):
        motif = rng.integers(0, cfg.vocab_size, 12).tolist()
        reqs.append(Request(rid=i, prompt=(motif * n)[:n],
                            max_new_tokens=MAX_NEW))
    return reqs


def serve(label: str, engine, requests, cfg, dev) -> None:
    """Warm up every program the run uses, serve ``requests``, check them."""
    from repro.serving.engine import Request
    rng = np.random.default_rng(SEED + 1)
    warm = Request(rid=10_000, prompt=rng.integers(
        0, cfg.vocab_size, CHUNK + 16).tolist(), max_new_tokens=2)
    t0 = time.perf_counter()
    engine.submit(warm)
    engine.run_until_done()
    compile_s = time.perf_counter() - t0
    if not warm.done:
        raise RuntimeError(f"{label}: warm-up request did not finish")

    t0 = time.perf_counter()
    for r in requests:
        engine.submit(r)
    engine.run_until_done()
    run_s = time.perf_counter() - t0

    unfinished = [r.rid for r in requests
                  if not r.done or r.state != "done"
                  or len(r.output) != r.max_new_tokens]
    if unfinished:
        raise RuntimeError(f"{label}: requests {unfinished} did not finish")
    trips = engine.kv_stats["guard_trips"]
    if trips:
        raise RuntimeError(f"{label}: {trips} numerics-guard trips")
    lp = np.concatenate([np.asarray(r.logprobs, np.float64)
                         for r in requests])
    if lp.size != sum(r.max_new_tokens for r in requests) \
            or not np.all(np.isfinite(lp)):
        raise RuntimeError(f"{label}: missing or non-finite logprobs")
    tokens = sum(len(r.output) for r in requests)
    log(f"smoke ({label}): {len(requests)} requests, prompts "
        f"{min(len(r.prompt) for r in requests)}.."
        f"{max(len(r.prompt) for r in requests)} tok, {tokens} tokens "
        f"emitted; warm-up (compile) {compile_s:.1f} s, run {run_s:.1f} s "
        f"({tokens / run_s:.1f} tok/s); steps {engine._step_count}; "
        f"peak device memory {peak_gib(dev)}; mean logprob "
        f"{lp.mean():.3f}")


def kernels_in(label: str, jitted, args, want: tuple[str, ...]) -> str:
    """Compile ``jitted`` at ``args`` and require a ``tpu_custom_call``
    for each kernel name in ``want`` (Pallas names the call site).
    Returns the optimized HLO text."""
    t0 = time.perf_counter()
    compiled = jitted.lower(*args).compile()
    text = compiled.as_text()
    mem = compiled.memory_analysis()
    found: dict[str, int] = {}
    for line in text.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        op = re.search(r'op_name="([^"]*)"', line)
        for name in want:
            if op and name in op.group(1):
                found[name] = found.get(name, 0) + 1
    missing = [n for n in want if n not in found]
    if missing:
        raise RuntimeError(f"(e) {label}: no tpu_custom_call for {missing}")
    log(f"smoke (e) {label}: tpu_custom_call "
        + (", ".join(f"{n} x{found[n]}" for n in want) or "none required")
        + f" (compile {time.perf_counter() - t0:.1f} s; arguments "
        f"{mem.argument_size_in_bytes / 2**30:.2f} GiB, output "
        f"{mem.output_size_in_bytes / 2**30:.2f} GiB, temporaries "
        f"{mem.temp_size_in_bytes / 2**30:.2f} GiB)")
    return text


def pool_layouts(label: str, text: str, caches) -> None:
    """Count the copies in optimized HLO ``text`` whose shape is a pool
    layer's or a stacked pool's. A K/V payload pool copied into a
    non-default layout is a relayout of a whole pool inside the launch,
    and fails the phase; the quantized scale tiles [nb, bs, hkv] (hkv of
    128 lanes) are still re-laid out, and are only counted."""
    from jax.tree_util import tree_flatten_with_path
    from repro.ecm.hlo_cost import pool_copies
    from repro.models.paged import POOL_KEYS
    leaves: dict[tuple, str] = {}       # one layer's shape -> pool leaves
    for path, leaf in tree_flatten_with_path(caches)[0]:
        name = str(getattr(path[-1], "key", path[-1]))
        if name in POOL_KEYS:
            shape = leaf.shape[1:]
            leaves[shape] = "/".join(filter(None, (leaves.get(shape), name)))
    nb = next(iter(leaves))[0]
    copies = pool_copies(text, nb)
    relaid: dict[str, list[str]] = {}
    for c in copies:
        if not c.default:
            names = next((n for shape, n in leaves.items()
                          if c.dims[-len(shape):] == shape), "?")
            relaid.setdefault(names, []).append(f"{c.name} {c.dims}")
    log(f"smoke (e) {label}: {len(copies)} pool-shaped copies, into a "
        f"non-default layout: "
        + (", ".join(f"{n} x{len(v)}" for n, v in relaid.items()) or "0"))
    payload = {n: v for n, v in relaid.items()
               if n == "?" or "kpool" in n or "vpool" in n}
    if payload:
        raise RuntimeError(f"(e) {label}: pool relayouts {payload}")


def decode_args(engine):
    import jax.numpy as jnp
    live = jnp.ones((SLOTS,), bool)
    return (engine.params, (jnp.asarray(engine._next_tokens), live),
            engine.caches)


def prefill_chunk_args(engine):
    import jax.numpy as jnp
    return (engine.params, jnp.zeros((1, CHUNK), jnp.int32), engine.caches,
            jnp.int32(0), jnp.int32(0))


def verify_args(engine):
    import jax.numpy as jnp
    window = engine.spec_k + 1
    return (engine.params, jnp.zeros((SLOTS, window), jnp.int32),
            engine.caches, jnp.arange(SLOTS, dtype=jnp.int32),
            jnp.zeros((SLOTS,), jnp.int32))


def phase_engine(label, cfg, params, dev, *, spec: bool):
    import jax.numpy as jnp
    from repro.serving import engine as engine_mod
    kw = dict(max_slots=SLOTS, max_context=MAX_CONTEXT, block_size=BLOCK,
              prefill_chunk=CHUNK)
    rng = np.random.default_rng(SEED)
    if spec:
        from repro.spec import NGramProposer
        eng = engine_mod.SpecDecodeEngine(cfg, params,
                                          proposer=NGramProposer(),
                                          spec_k=SPEC_K, **kw)
        reqs = make_repetitive_requests(cfg, SPEC_PROMPT_LENS, rng)
    else:
        eng = engine_mod.DecodeEngine(cfg, params, **kw)
        reqs = make_requests(cfg, PROMPT_LENS, rng)
    serve(label, eng, reqs, cfg, dev)
    if spec:
        log(f"smoke ({label}): drafted {eng.kv_stats['spec_drafted']}, "
            f"accepted {eng.kv_stats['spec_accepted']} "
            f"({eng.acceptance_rate:.2f}), "
            f"{eng.mean_accepted_length:.2f} tok per verify walk")
        kernels_in(f"verify[{label}]", eng._verify, verify_args(eng),
                   ("paged_attention",))
        window = SPEC_K + 1
        kernels_in(f"logit_stats[{label}]", engine_mod._logit_stats,
                   (jnp.zeros((SLOTS * window, cfg.vocab_size), jnp.float32),
                    jnp.zeros((SLOTS * window,), jnp.int32)),
                   ("fused_reduce_rows",))
    else:
        pool_layouts(f"decode[{label}]",
                     kernels_in(f"decode[{label}]", eng._decode,
                                decode_args(eng),
                                ("paged_attention", "fused_reduce_rows")),
                     eng.caches)
        pool_layouts(f"prefill_chunk[{label}]",
                     kernels_in(f"prefill_chunk[{label}]",
                                eng._prefill_chunk, prefill_chunk_args(eng),
                                ()),
                     eng.caches)
    del eng


def _median_ms(fn, reps: int = 5) -> float:
    """Median wall time of ``fn()`` (which returns a device array) after
    one untimed call, each call waited for with block_until_ready."""
    fn().block_until_ready()
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        fn().block_until_ready()
        times.append(time.perf_counter() - t0)
    return 1e3 * float(np.median(times))


def _rel_err(got, want) -> float:
    """Worst row's ||got - want||_2 / ||want||_2 over [..., V] logits."""
    got = np.asarray(got, np.float64).reshape(-1, got.shape[-1])
    want = np.asarray(want, np.float64).reshape(-1, want.shape[-1])
    return float(np.max(np.linalg.norm(got - want, axis=-1)
                        / np.linalg.norm(want, axis=-1)))


def phase_reference(cfg, params, dev, *, prompt_len: int = 1000) -> None:
    """(f): kernel path vs gather + flash on the same prefilled caches."""
    import jax
    import jax.numpy as jnp
    from repro.models import api, attention, paged
    from repro.models.paged import PagedLayout
    from repro.serving.engine import _logit_stats

    layout = PagedLayout.for_context(MAX_CONTEXT, BLOCK)
    key = jax.random.key(SEED + 2)
    tokens = jax.random.randint(key, (SLOTS, prompt_len), 0, cfg.vocab_size)
    t0 = time.perf_counter()
    _, caches = jax.jit(api.prefill_fn(cfg, layout))(
        params, {"tokens": tokens})
    # ragged lengths with mid-block tails: rows past a slot's length are
    # masked, and the decode append overwrites the row at the length
    lens = jnp.asarray([prompt_len - 1 - (prompt_len // SLOTS) * i
                        for i in range(SLOTS)], jnp.int32)
    caches = paged.set_lens(caches, jnp.arange(SLOTS, dtype=jnp.int32), lens)
    step = jax.random.randint(jax.random.fold_in(key, 1), (SLOTS, 1), 0,
                              cfg.vocab_size)
    window = jax.random.randint(jax.random.fold_in(key, 2),
                                (SLOTS, SPEC_K + 1), 0, cfg.vocab_size)
    slots = jnp.arange(SLOTS, dtype=jnp.int32)

    def run_paths(label):
        dec = jax.jit(api.decode_fn(cfg))
        ver = jax.jit(api.verify_fn(cfg))
        run_dec = lambda: dec(params, step, caches)[0]
        run_ver = lambda: ver(params, window, caches, slots, lens)[0]
        log(f"smoke (f) {label} path: decode step "
            f"{_median_ms(run_dec):.2f} ms, verify step "
            f"{_median_ms(run_ver):.2f} ms (median of 5, lens "
            f"{int(lens.min())}..{int(lens.max())})")
        return (np.asarray(run_dec(), np.float32),
                np.asarray(run_ver(), np.float32))

    kernel = run_paths("kernel")
    enabled = attention.paged_kernel_enabled
    attention.paged_kernel_enabled = lambda: False
    try:
        gather = run_paths("gather+flash")
    finally:
        attention.paged_kernel_enabled = enabled
    for name, got, want in zip(("decode", "verify"), kernel, gather):
        err = _rel_err(got, want)
        agree = float(np.mean(got.argmax(-1) == want.argmax(-1)))
        log(f"smoke (f) {name} logits, kernel vs gather+flash: "
            f"worst row |d|/|ref| = {err:.3e} (tol {LOGIT_RTOL:g}), argmax "
            f"agreement {agree:.3f}")
        if not np.all(np.isfinite(got)) or err > LOGIT_RTOL:
            raise RuntimeError(f"(f) {name}: kernel path disagrees with "
                               f"the gather + flash reference ({err:.3e})")

    rows = jnp.asarray(kernel[0].reshape(SLOTS, -1))
    toks = jnp.argmax(rows, axis=-1).astype(jnp.int32)
    st = {k: np.asarray(v, np.float64)
          for k, v in _logit_stats(rows, toks).items()}
    lse = np.asarray(jax.nn.logsumexp(rows, axis=-1), np.float64)
    mx = np.asarray(jnp.max(rows, axis=-1), np.float64)
    mean = np.asarray(jnp.mean(rows, axis=-1), np.float64)
    mean_abs = np.asarray(jnp.mean(jnp.abs(rows), axis=-1), np.float64)
    d_lse = float(np.max(np.abs(st["logsumexp"] - lse) / (1.0 + np.abs(lse))))
    d_mean = float(np.max(np.abs(st["mean"] - mean) / mean_abs))
    exact_max = bool(np.array_equal(st["max"], mx))
    log(f"smoke (f) _logit_stats vs jnp: logsumexp {d_lse:.3e}, "
        f"mean {d_mean:.3e} (tol {STAT_RTOL:g}), max exact {exact_max}; "
        f"{time.perf_counter() - t0:.1f} s incl. compile; peak device "
        f"memory {peak_gib(dev)}")
    if not exact_max or d_lse > STAT_RTOL or d_mean > STAT_RTOL:
        raise RuntimeError("(f) _logit_stats disagrees with jnp")


def main() -> None:
    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":                                 # (a)
        raise SystemExit(f"chip_smoke: needs a TPU; JAX found "
                         f"{dev.platform!r} ({dev.device_kind})")
    log(f"smoke (a): {dev.platform} {dev.device_kind}, "
        f"{len(devices)} device(s); compile cache {cache_dir}")

    from repro.configs import get_config
    from repro.models import api, common
    cfg = get_config(ARCH)
    t0 = time.perf_counter()
    params = jax.jit(lambda k: common.init_params(api.schema(cfg), k))(
        jax.random.key(SEED))
    jax.block_until_ready(params)
    n_params = sum(x.size for x in jax.tree.leaves(params))
    log(f"smoke: {ARCH} at published widths ({cfg.num_layers} layers, "
        f"d_model {cfg.d_model}, {cfg.num_heads} heads, vocab "
        f"{cfg.vocab_size}), {n_params / 1e6:.0f}M random params in "
        f"{time.perf_counter() - t0:.1f} s; peak device memory "
        f"{peak_gib(dev)}")

    phase_engine("b bf16", cfg, params, dev, spec=False)
    phase_engine("c int8", cfg.with_(kv_dtype="int8"), params, dev,
                 spec=False)
    phase_engine("d spec ngram", cfg, params, dev, spec=True)
    phase_reference(cfg, params, dev)

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)


if __name__ == "__main__":
    main()
