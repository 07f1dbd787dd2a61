"""A CPU rehearsal of one cell at reduced size: the whole run, the
control that must come out not correct, and no device metric written
from a CPU run."""

import json
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))

from rehearsal import rehearse, tiny_cell  # noqa: E402

from bench import check, spec  # noqa: E402

SEED = 2**31 + 4242
DEVICE = {m["name"] for m in spec.load_json(spec.ROOT / "BENCHMARK.json")
          ["per_layer"] if m["source"] == "device_trace"}


@pytest.fixture(scope="module")
def plain():
    out = {}
    return rehearse(SEED, out=out), out


def test_rehearsal_serves_the_window_and_is_correct(plain):
    res, out = plain
    assert list(res) == ["correct", "attempted", "failed", "metrics",
                         "device", "compared"]
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] == round(12.0 * 1.5)
    assert set(res["metrics"]) == {"ttft_p90_ms", "itl_p95_ms", "setup_s"}
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert res["device"]["platform"] == "cpu"
    for name, c in res["compared"].items():
        assert c["value"] <= c["limit"], name
    json.dumps(res)


@pytest.fixture(scope="module")
def controls(plain):
    res, out = plain
    config = tiny_cell().config
    picked = check.sample(out["served"], SEED, 10**6)
    nums = check.compare(config, SEED, picked, out["schedule"].max_tokens(),
                         controls=tuple(check.CONTROLS))
    return nums, config["correct"]["limits"]


def test_control_one_precision_below_is_not_correct(controls):
    nums, limits = controls
    assert check.verdict(nums, limits)[0] is True
    fp8 = nums["controls"]["fp8"]
    assert check.verdict(fp8, limits)[0] is False
    assert fp8["logit_gap"] >= 3 * nums["logit_gap"]


def test_logit_stage_control_reads_bf16_rounding(controls):
    """The bf16 logit-statistics control departs from the reference by
    about bfloat16's rounding of the logprob, and no more."""
    nums, _ = controls
    c = nums["controls"]["bf16_stats"]
    assert 0 < c["logprob_err"] < 0.1
    assert c["logit_gap"] < 0.1


def test_backlog_rehearsal_drains_what_the_window_started():
    out = {}
    e2e = [{"name": n, "unit": u} for n, u in
           (("tok_s", "tokens/s"), ("itl_p95_ms", "ms"), ("setup_s", "s"))]
    cell = tiny_cell("tiny_offline", end_to_end=e2e, per_layer=[])
    res = rehearse(SEED + 2, out=out, cell=cell)
    assert res["correct"] is True and res["failed"] == 0
    assert set(res["metrics"]) == {"tok_s", "itl_p95_ms", "setup_s"}
    assert res["metrics"]["tok_s"]["value"] > 0
    # every request with a token in the window finished in the drain
    assert len(out["served"]) == res["attempted"] > 0


def test_traced_rehearsal_writes_no_device_metric():
    res = rehearse(SEED + 1, trace=True)
    assert res["correct"] is True
    assert not DEVICE & set(res["metrics"])
    assert "busy_s" not in res["device"]
    assert {"gen_lag_p95_ms", "queue_wait_p50_ms"} <= set(res["metrics"])
    assert "breakdown" not in res           # no device trace on the CPU
