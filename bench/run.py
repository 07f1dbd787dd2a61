"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout. The run enables the persistent compile
cache, requires a TPU with as many chips as the cell asks for (it never
falls back to the CPU), makes the cell's weights on the device from the
seed, warms up the cell's own shapes, serves the cell's traffic for
``--seconds`` of measured window and checks what the window served
against the plain reference. Weights, reference and work counts are
those of the model family that the cell's configuration names
(``bench/families/<family>.py``). The last line of standard output is one
JSON object: ``correct``, ``attempted``, ``failed``, ``metrics``,
``device`` (and with ``--trace 1`` ``breakdown``), then ``compared``: each
number compared beside its limit, which are also the last lines of
standard error. With ``--trace 0`` the metrics are the cell's end-to-end
metrics, with ``--trace 1`` its per-layer metrics, read from a profiler
trace of the window's last few seconds.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
for p in (ROOT / "src", ROOT):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from bench import check, loadgen, spec  # noqa: E402
from bench import trace as tracemod  # noqa: E402
from bench.drive import Driver  # noqa: E402

TRACE_SECONDS = 4.0
WARM_RID = 1 << 40


class NoAccelerator(RuntimeError):
    pass


def program_config(config: dict):
    """The program's ModelConfig for ``config``, checked against what its
    family wants of it, and the family's sizes."""
    from repro.configs import get_config
    prog = config["program"]
    cfg = get_config(prog["arch"]).with_(
        **prog.get("overrides", {}), kv_dtype=config["engine"]["kv_dtype"])
    family = spec.load_family(config)
    m = family.dims(config)
    want = family.program_want(m)
    bad = {k: (getattr(cfg, k), v) for k, v in want.items()
           if getattr(cfg, k) != v}
    if bad:
        raise ValueError(f"program config departs from {config['name']}: "
                         f"{bad} (program, configuration)")
    return cfg, m


def check_layout(cfg, params) -> None:
    """The weights' tree has the program's names and shapes."""
    import jax
    from repro.models import api, common
    want = common.abstract_params(api.schema(cfg))
    got = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                       params)
    if jax.tree.structure(want) != jax.tree.structure(got) or any(
            a.shape != b.shape for a, b in zip(jax.tree.leaves(want),
                                               jax.tree.leaves(got))):
        raise ValueError("the family's weights differ from the program's "
                         "parameter schema")


def warm_up(engine, Request, chunk: int, vocab: int, seed: int) -> None:
    """Serve one request for each prompt length 1..chunk: every prefill
    chunk shape the traffic can make (a full chunk and each tail), the
    first-token pass, and the batched decode step beside prefill."""
    rng = loadgen.rng_for(seed, 4)
    for n in range(1, chunk + 1):
        engine.submit(Request(rid=WARM_RID + n,
                              prompt=rng.integers(0, vocab, n).tolist(),
                              max_new_tokens=2))
    engine.run_until_done()


TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"


def watch_tracing(jax) -> tuple[list, object]:
    """The host time of every jaxpr trace from here on (a program traced
    for a new shape, then compiled or loaded from the cache), and the
    listener to unregister."""
    times: list[float] = []

    def listener(event, duration, **_):
        if event == TRACE_EVENT:
            times.append(time.perf_counter())
    jax.monitoring.register_event_duration_secs_listener(listener)
    return times, listener


def device_info(devices) -> dict:
    dev = devices[0]
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices)}


def memory_peak(devices) -> int | None:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def run_cell(cell: spec.Cell, seed: int, seconds: float, trace: bool, *,
             require_accelerator: bool = True, t_process: float = T_PROCESS,
             compile_cache: bool = True, engine_hook=None,
             log=None, out: dict | None = None,
             controls: tuple = ()) -> dict:
    """One run of ``cell``; returns the result object. For tests and
    ``bench/calibrate.py``: ``engine_hook`` may alter the engine before
    the window is served, ``out`` receives the served requests, the
    schedule and the compared numbers, and ``controls`` names the controls
    (``check.CONTROLS``) whose numbers are also read into ``out``."""
    log = log or (lambda msg: print(msg, file=sys.stderr, flush=True))
    cache_dir = None
    if compile_cache:
        from repro.launch.compile_cache import enable_compile_cache
        cache_dir = enable_compile_cache()
    import jax
    if compile_cache:
        # cache every program, however fast it compiled, so that only a
        # cell's first run in a checkout compiles
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    devices = jax.devices()
    if require_accelerator and (devices[0].platform != "tpu"
                                or len(devices) < cell.chips):
        raise NoAccelerator(
            f"cell {cell.name} needs {cell.chips} TPU chip(s); JAX found "
            f"{len(devices)} {devices[0].platform} device(s)")
    log(f"bench: {cell.name} seed {seed} on {devices[0].device_kind}, "
        f"compile cache {cache_dir}")
    from repro.serving.engine import DecodeEngine, Request

    config = cell.config
    family = spec.load_family(config)
    cfg, dims = program_config(config)
    geo = config["engine"]
    stamp = lambda what: log(f"bench: {what} at "
                             f"{time.perf_counter() - t_process:.2f} s")
    stamp("devices ready")
    params = family.make_params(dims, seed)
    jax.block_until_ready(params)
    stamp("weights made")
    check_layout(cfg, params)
    engine = DecodeEngine(cfg, params, max_slots=geo["max_slots"],
                          max_context=geo["max_context"],
                          block_size=geo["block_size"],
                          prefill_chunk=geo["prefill_chunk"])
    stamp("engine built")
    warm_up(engine, Request, geo["prefill_chunk"], dims.vocab, seed)
    stamp("warm-up served")
    if engine_hook is not None:
        engine_hook(engine)
    schedule = loadgen.Schedule(cell.mix, seed, seconds, dims.vocab,
                                geo["max_slots"])

    capture = span = trace_at = on_trace = None
    if trace:
        capture = tracemod.Capture()
        span = jax.profiler.TraceAnnotation
        # the last seconds of the window: collecting the profile holds
        # the host for seconds, which then falls after the window closes
        trace_at = (max(0.0, seconds - TRACE_SECONDS), seconds)

        def on_trace(start: bool) -> None:
            jax.block_until_ready(engine.caches)
            t = time.perf_counter()
            capture.start() if start else capture.stop()
            stamp(f"profiler {'started' if start else 'stopped'} in "
                  f"{time.perf_counter() - t:.3f} s,")

    driver_kw = dict(span=span, trace_at=trace_at, on_trace=on_trace) \
        if trace else {}
    traced_at, listener = watch_tracing(jax)
    rec = Driver(engine, schedule, seconds, request_cls=Request,
                 **driver_kw).run()
    jax.monitoring.unregister_event_duration_listener(listener)
    log(f"bench: {sum(rec.t0 <= t < rec.t1 for t in traced_at)} programs "
        f"traced inside the window, {len(traced_at)} after warm-up")
    setup_s = rec.t0 - t_process
    peak = memory_peak(devices[:cell.chips])
    window = rec.window_tracks()
    served = [check.Served(t.arrival.rid, t.arrival.prompt,
                           list(t.req.output), list(t.req.logprobs))
              for t in window if t.req.state == "done"]
    unserved = [t.req for t in window if t.req.state != "done"]
    for req in unserved:
        log(f"bench: request {req.rid} ended {req.state} after "
            f"{len(req.output)} of {req.max_new_tokens} tokens"
            + (f" ({req.error})" if req.error else ""))
    failed = len(unserved)
    # free the program's state before the reference runs on the chip
    del engine, params, on_trace, driver_kw
    gc.collect()

    events = None
    if trace:
        events = tracemod.extract(capture.xspace)
        del capture

    # what a metric reader may read (bench/metrics/<name>.py)
    view = types.SimpleNamespace(
        run=rec, dims=dims, family=family, engine=geo, setup_s=setup_s,
        events=events,
        peak=peak_entry(devices[0].device_kind)
        if devices[0].platform == "tpu" else None)
    wanted = cell.per_layer if trace else cell.end_to_end
    metrics = {}
    for m in wanted:
        value = spec.load_reader(m["name"])(view)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    if out is not None:
        out.update(served=served, schedule=schedule)
    limits = config["correct"]["limits"]
    picked = check.sample(served, seed, config["correct"]["sample_tokens"])
    numbers = check.compare(config, seed, picked, schedule.max_tokens(),
                            controls=controls)
    if out is not None:
        out["numbers"] = numbers
    ok, compared = check.verdict(numbers, limits)
    stamp("reference compared")
    log(f"bench: reference compared {numbers['tokens']} served tokens of "
        f"{numbers['requests']} requests")
    dev = device_info(devices[:cell.chips])
    dev["memory_peak_bytes"] = peak
    result = {"correct": bool(ok and failed == 0), "attempted": len(window),
              "failed": failed, "metrics": metrics, "device": dev}
    if trace and tracemod.device_planes(events):
        lo, hi = tracemod.window(events)
        dev["busy_s"] = tracemod.busy_ns(events) / 1e9
        dev["window_s"] = (hi - lo) / 1e9
        result["breakdown"] = {"device_ops": tracemod.top_ops(events),
                               "idle_gaps": tracemod.idle_gaps(events)}
    result["compared"] = compared
    return result


def peak_entry(kind: str) -> dict:
    peaks = spec.load_json(spec.BENCH_DIR / "peaks.json")
    if kind not in peaks:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    return peaks[kind]


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    cell = spec.load_cell(args.workload)
    try:
        result = run_cell(cell, args.seed, args.seconds, bool(args.trace))
    except NoAccelerator as e:
        print(f"bench: {e}", file=sys.stderr, flush=True)
        return 2
    for name, c in result["compared"].items():
        print(f"compared {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
