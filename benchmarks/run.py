"""Benchmark harness: one module per paper table/figure, CSV output
``name,us_per_call,derived`` per row.

  bench_ecm_predictions   paper §4 / Eqs. 1-3 (ECM cycle predictions)
  bench_accuracy          paper §1 motivation (error vs N, naive vs Kahan)
  bench_kernel_throughput paper Figs. 5-7 analog + unroll (U) sweep,
                          measured vs ECM-predicted (repro.ecm.tpu)
  bench_scaling           paper Figs. 8-9 analog (saturation curves)
  bench_tpu_kahan         DESIGN.md §2.3 (the paper's question on v5e)
  bench_collectives       compensated all-reduce numerics + bandwidth model
  bench_serving           paged-KV engine: tok/s + KV-bytes-touched
  bench_quant             quantized KV pools: tok/s + bytes + ppl proxy
                          vs kv_dtype, measured vs ECM-predicted speedup
  bench_spec              speculative serving: tok/s + acceptance rate vs
                          the ECM walk-bookkeeping forecast, across
                          proposers / prompt mixes / kv_dtypes / k
  roofline_report         §Roofline table from the dry-run artifacts
                          (one row per cell); with no artifacts, falls
                          back to LIVE attribution rows from a profiled
                          engine (roofline/live/<phase>)

CLI:
  --only SUBSTR   run only modules whose name contains SUBSTR (repeatable)
  --json [PATH]   also write rows as JSON [{name, us_per_call, derived}]
                  — the CI smoke step's perf-trajectory artifact. With no
                  PATH the name is derived deterministically from the git
                  commit (BENCH_<shortsha>.json) so the CI workflow can
                  commit it and the trajectory accumulates in-repo.
  --compare PREV.json
                  regression gate, two tiers. DETERMINISTIC COUNTER
                  series (bytes/tokens moved, hit rates, acceptance
                  rates — the ``kv_stats``-derived fields listed in
                  ``DETERMINISTIC_FIELDS``, plus counter-basis
                  ``ecm_residual/`` rows) must match the previous
                  trajectory to ~1e-6 relative: a seeded workload
                  reproduces them bitwise, so any mismatch is a real
                  code/workload change and the gate exits nonzero.
                  WALL-CLOCK series (``tok_s=``) that lost more than
                  --compare-tolerance while every counter still matches
                  are reported as ``# POSSIBLE HOST DRIFT`` without
                  failing — counters unmoved means the engine did the
                  same work, so the delta lives on the host, not in the
                  code. Series only one side has are ignored (benches
                  come and go); CI feeds the last committed BENCH_*.json.

                  Drift calibration: every run opens with a
                  ``calibration/kahan_dot_ref`` row — a pinned-shape
                  Kahan-dot reference kernel whose ratio to the
                  committed constant (repro.obs.profile
                  .CALIBRATION_REF_S) is this run's
                  ``host_drift_factor``, stamped on every wallclock row
                  and residual. The gate normalizes both sides' tok/s
                  by their factors before judging drift: a loss that
                  disappears under normalization is drift-EXPLAINED
                  (the reference kernel slowed down by the same ratio)
                  and, when every drift line is explained, the run
                  exits with the distinct code ``DRIFT_EXIT_CODE`` (4)
                  so CI can tell "host was slow" from "code got slow".
                  Counter-basis rows stay gated at 1e-6 regardless.
  --compare-tolerance FRAC   allowed fractional tok/s loss before a
                  host-drift report (default 0.20)
"""

from __future__ import annotations

import argparse
import json
import re
import subprocess
import traceback

from benchmarks import (bench_accuracy, bench_collectives,
                        bench_ecm_predictions, bench_kernel_throughput,
                        bench_quant, bench_scaling, bench_serving,
                        bench_spec, bench_tpu_kahan, roofline_report)
from repro.launch.compile_cache import enable_compile_cache

MODULES = [
    bench_ecm_predictions,
    bench_accuracy,
    bench_kernel_throughput,
    bench_scaling,
    bench_tpu_kahan,
    bench_collectives,
    bench_serving,
    bench_quant,
    bench_spec,
    roofline_report,
]


def default_json_path() -> str:
    """Deterministic perf-trajectory filename for the current commit —
    the same commit always maps to the same BENCH_*.json, so re-runs
    overwrite instead of multiplying artifacts."""
    try:
        sha = subprocess.run(["git", "rev-parse", "--short", "HEAD"],
                             capture_output=True, text=True,
                             timeout=10).stdout.strip()
    except Exception:
        sha = ""
    return f"BENCH_{sha or 'local'}.json"


def _tok_s(derived: str) -> float | None:
    m = re.search(r"\btok_s=([0-9.]+)", derived or "")
    return float(m.group(1)) if m else None


# The drift-calibration anchor row every trajectory JSON opens with, and
# the distinct exit code --compare uses when host drift (not a code
# regression) explains every flagged tok/s loss.
CALIBRATION_ROW = "calibration/kahan_dot_ref"
DRIFT_EXIT_CODE = 4


def calibration_row() -> tuple:
    """Measure the pinned-shape Kahan-dot reference at bench start; the
    ratio to the committed constant is this run's host_drift_factor."""
    from repro.obs import profile as obs_profile
    cal = obs_profile.calibrate()
    return (CALIBRATION_ROW, f"{cal.ref_s * 1e6:.0f}",
            f"host_drift_factor={cal.host_drift_factor:.3f}"
            f" dispatch_us={cal.dispatch_s * 1e6:.1f}"
            f" machine_scale={cal.machine_scale:.1f}"
            f" elems={cal.elems}")


def _is_wallclock_row(derived: str) -> bool:
    """Rows whose headline numbers come off the wall clock — the ones
    that carry (and can be normalized by) a host_drift_factor."""
    return ("tok_s=" in (derived or "")
            or "basis=wallclock" in (derived or ""))


def _drift_factor(rows: list[dict]) -> float | None:
    """The host_drift_factor recorded by a trajectory's calibration row
    (None for pre-calibration trajectories)."""
    for r in rows:
        if r.get("name") == CALIBRATION_ROW:
            f = _fields(r.get("derived", "")).get("host_drift_factor")
            if f:
                return f
    return None


# key=value fields in derived strings; numeric values may carry an 'x'
# suffix (ratios) and scientific notation.
_FIELD_RE = re.compile(
    r"\b([A-Za-z_][A-Za-z0-9_]*)=(-?[0-9]+(?:\.[0-9]+)?(?:e[+-]?[0-9]+)?)x?"
    r"(?=\s|$)")

# Derived fields computed purely from the engines' deterministic
# counters (kv_stats / swap / prefix-cache / spec accounting) on seeded
# workloads. These reproduce to the printed precision on any host —
# a mismatch against the previous trajectory is a code or workload
# change, never noise, so the compare gate hard-fails on it.
# Wall-clock-derived fields (tok_s, speedup, read_gbps, us_per_call)
# are deliberately NOT here.
DETERMINISTIC_FIELDS = frozenset({
    "paged_kv_kib", "contig_kv_kib", "kv_reduction", "prefix_hit",
    "hit_rate", "prefill_tok_reduction", "saved_kv_kib", "cow_blocks",
    "preempted", "swapped_blocks", "restored_blocks", "guard_trips",
    "host_kib", "acc", "E", "elems",
    # session-KV counters (serving/session rows): turn-2+ whole-history
    # hit tokens/rate, spill-tier traffic, and the promote-vs-never
    # prefill-token ratio — all derived from seeded token counters
    "turn2_hit", "turn2_hit_rate", "hit_rate_nopromote",
    "spilled_blocks", "promoted_blocks", "promoted_tokens",
    "promote_gain",
})


def _fields(derived: str) -> dict[str, float]:
    return {k: float(v) for k, v in _FIELD_RE.findall(derived or "")}


def _gated_counters(name: str, fields: dict) -> dict[str, float]:
    """The subset of a row's fields the deterministic gate covers.
    Counter-basis ``ecm_residual/`` rows gate their predicted AND
    measured sides (both are functions of deterministic inputs);
    wallclock-basis residuals gate nothing."""
    if name.startswith("ecm_residual/"):
        if fields.get("basis") == "counter":
            return {k: fields[k] for k in ("predicted", "measured")
                    if k in fields}
        return {}
    return {k: v for k, v in fields.items() if k in DETERMINISTIC_FIELDS}


def find_regressions(current: list[dict], prev_path: str,
                     tolerance: float = 0.20) -> tuple[list, list, int]:
    """Two-tier comparison against a previous trajectory JSON.

    Returns (counter_mismatches, drift, shared) where
    ``counter_mismatches`` is [(name, field, was, now)] for every
    deterministic counter that moved beyond ~1e-6 relative (hard
    failures), ``drift`` is [(name, was, now, explained)] for shared
    ``tok_s`` series that lost more than ``tolerance`` (reported as
    possible host drift — wall clock on shared runners is noisy, and
    with counters unmoved the engine provably did the same work), and
    ``shared`` is the shared-series count.

    ``explained`` is True when normalizing both sides by their runs'
    measured ``host_drift_factor`` (the calibration rows) brings the
    loss back inside ``tolerance`` — the reference kernel slowed by the
    same ratio the workload did, so the host, not the code, moved.
    False when normalization does NOT recover it, or when either side
    predates the calibration row (nothing to normalize by)."""
    with open(prev_path) as f:
        prev = json.load(f)
    ref = {r["name"]: r.get("derived", "") for r in prev}
    hdf_prev, hdf_now = _drift_factor(prev), _drift_factor(current)
    mismatches, drift, shared = [], [], 0
    for row in current:
        name = row["name"]
        if name not in ref:
            continue
        shared += 1
        prev_fields = _fields(ref[name])
        now_fields = _fields(row.get("derived", ""))
        # basis= is a word, not a number — recover it for residual rows
        for src, dst in ((ref[name], prev_fields),
                         (row.get("derived", ""), now_fields)):
            m = re.search(r"\bbasis=(\w+)", src or "")
            if m:
                dst["basis"] = m.group(1)
        gated = _gated_counters(name, now_fields)
        for field, now_v in gated.items():
            was_v = _gated_counters(name, prev_fields).get(field)
            if was_v is None:
                continue    # field newly added to the row format
            if abs(now_v - was_v) > 1e-6 * max(abs(was_v), 1e-9):
                mismatches.append((name, field, was_v, now_v))
        was, now = _tok_s(ref[name]), _tok_s(row.get("derived", ""))
        if was and now and now < was * (1.0 - tolerance):
            explained = False
            if hdf_prev and hdf_now:
                # normalize to reference-host tok/s: a slower host has
                # factor > 1, and tok_s * factor recovers what the
                # reference host would have measured
                explained = (now * hdf_now
                             >= was * hdf_prev * (1.0 - tolerance))
            drift.append((name, was, now, explained))
    return mismatches, drift, shared


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", action="append", default=None, metavar="SUBSTR",
                    help="run only modules whose name contains SUBSTR")
    ap.add_argument("--json", default=None, metavar="PATH", nargs="?",
                    const="auto",
                    help="also write results as JSON; omit PATH for the "
                         "deterministic per-commit BENCH_<shortsha>.json")
    ap.add_argument("--compare", default=None, metavar="PREV.json",
                    help="exit nonzero if any shared tok_s series lost "
                         "more than --compare-tolerance vs this trajectory")
    ap.add_argument("--compare-tolerance", type=float, default=0.20,
                    metavar="FRAC", help="allowed fractional tok/s loss")
    args = ap.parse_args()
    if args.json == "auto":
        args.json = default_json_path()
    enable_compile_cache()

    modules = MODULES
    if args.only:
        modules = [m for m in MODULES
                   if any(s in m.__name__ for s in args.only)]
        if not modules:
            raise SystemExit(f"--only {args.only}: no module matches "
                             f"(have {[m.__name__ for m in MODULES]})")

    print("name,us_per_call,derived")
    collected = []
    failures = 0
    # drift calibration first: the pinned Kahan-dot reference anchors
    # every wallclock row below to this host's measured speed
    hdf = None
    try:
        cal_row = calibration_row()
        print(",".join(str(c) for c in cal_row), flush=True)
        collected.append({"name": cal_row[0], "us_per_call": cal_row[1],
                          "derived": cal_row[2]})
        hdf = _fields(cal_row[2]).get("host_drift_factor")
    except Exception:
        failures += 1
        print("# FAILED calibration")
        traceback.print_exc()
    for mod in modules:
        try:
            for row in mod.run():
                derived = str(row[2]) if len(row) > 2 else ""
                if hdf is not None and _is_wallclock_row(derived):
                    derived += f" host_drift_factor={hdf:.3f}"
                print(",".join([str(row[0]), str(row[1]), derived]),
                      flush=True)
                collected.append({"name": row[0],
                                  "us_per_call": row[1],
                                  "derived": derived})
        except Exception:
            failures += 1
            print(f"# FAILED {mod.__name__}")
            traceback.print_exc()
    if args.json is not None:
        with open(args.json, "w") as f:
            json.dump(collected, f, indent=1)
        print(f"# wrote {len(collected)} rows to {args.json}")
    if args.compare is not None:
        mismatches, drift, shared = find_regressions(
            collected, args.compare, args.compare_tolerance)
        for name, field, was, now in mismatches:
            print(f"# COUNTER MISMATCH {name}: {field} {was:g} -> {now:g}")
        hdf_txt = f"{hdf:.3f}" if hdf is not None else "n/a"
        for name, was, now, explained in drift:
            verdict = ("drift-EXPLAINED: loss disappears after "
                       "host_drift_factor normalization" if explained
                       else "NOT explained by measured drift")
            print(f"# POSSIBLE HOST DRIFT {name}: tok_s {was:.1f} -> "
                  f"{now:.1f} ({now / was - 1.0:+.0%}) "
                  f"host_drift_factor={hdf_txt} — deterministic "
                  f"counters unchanged, so the engine did the same "
                  f"work; {verdict}")
        if mismatches:
            raise SystemExit(
                f"{len(mismatches)} deterministic counter(s) moved vs "
                f"{args.compare} — seeded workloads reproduce these "
                f"bitwise; this is a code or workload change, not noise")
        print(f"# compare vs {args.compare}: {shared} shared series, "
              f"counters match; {len(drift)} possible host-drift "
              f"series (>{args.compare_tolerance:.0%} tok/s loss, "
              f"not gating)")
        if drift and all(x[3] for x in drift):
            # every flagged loss is the host's, not the code's: exit
            # with the distinct drift code so CI can record (and
            # tolerate) a slow-runner episode explicitly
            print(f"# exiting {DRIFT_EXIT_CODE}: host drift explains "
                  f"every flagged series")
            raise SystemExit(DRIFT_EXIT_CODE)
    if failures:
        raise SystemExit(failures)


if __name__ == "__main__":
    main()
