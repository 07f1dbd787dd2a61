"""Find a cell's knee: serve its mix at several fixed rates, one process.

    python3 bench/sweep.py --workload <cell> --seed <n> --seconds <s> \\
        --rates 1.0 1.4 1.8

For each rate the cell's mix is served open loop for a ramp and
``--seconds`` of window, then drained for at most the mix's ``drain_s``.
One JSON line per rate: arrivals, how many finished, time to first token
(p50, p90), token gaps (p50, p95) and the requests still waiting when the
window closed. The knee is the highest rate whose waiting queue does not
grow through the window; a cell's rate is fixed at about 0.8 of it.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
for p in (ROOT / "src", ROOT):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from bench import loadgen, spec, stats, views  # noqa: E402
from bench import run as runmod  # noqa: E402
from bench.drive import Driver  # noqa: E402

DRAIN_S = 15.0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    import jax
    if jax.devices()[0].platform != "tpu":
        print("sweep: needs a TPU", file=sys.stderr)
        return 2
    from repro.serving.engine import DecodeEngine, Request
    cfg, dims = runmod.program_config(cell.config)
    geo = cell.config["engine"]
    params = spec.load_family(cell.config).make_params(dims, args.seed)
    engine = DecodeEngine(cfg, params, max_slots=geo["max_slots"],
                          max_context=geo["max_context"],
                          block_size=geo["block_size"],
                          prefill_chunk=geo["prefill_chunk"])
    runmod.warm_up(engine, Request, geo["prefill_chunk"], dims.vocab,
                   args.seed)
    for i, r in enumerate(args.rates):
        mix = spec.merge(cell.mix, {"arrival": {"kind": "poisson",
                                                "rate_per_s": r},
                                    "drain_s": DRAIN_S})
        sch = loadgen.Schedule(mix, args.seed + i, args.seconds, dims.vocab,
                               geo["max_slots"])
        waiting_at_close = []

        def probe(start: bool, _w=waiting_at_close):
            _w.append(sum(1 for q in engine.scheduler.waiting))

        t = time.perf_counter()
        rec = Driver(engine, sch, args.seconds, request_cls=Request,
                     trace_at=(0.0, args.seconds), on_trace=probe).run()
        view = type("V", (), {"run": rec})()
        ttft, itl = views.ttft_ms(view), views.itl_ms(view)
        win = rec.window_tracks()
        engine.cancel_all()
        q = lambda xs, p: stats.percentile(xs, p) if xs else None
        print(json.dumps({
            "rate": r, "arrivals": len(win),
            "finished": sum(t.req.state == "done" for t in win),
            "ttft_p50_ms": q(ttft, 50), "ttft_p90_ms": q(ttft, 90),
            "itl_p50_ms": q(itl, 50), "itl_p95_ms": q(itl, 95),
            "waiting_at_open": waiting_at_close[0],
            "waiting_at_close": waiting_at_close[-1],
            "steps": len(views.window_steps(view)),
            "seconds": time.perf_counter() - t}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
