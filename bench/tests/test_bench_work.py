"""Work counts from problem shapes, and the peak table."""

import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import spec, views  # noqa: E402
from bench.work import fused_reduce_rows, model_step, paged_attention  # noqa: E402


def test_paged_attention_depends_only_on_lengths_heads_and_pool_dtype():
    lens = [17, 300, 1, 2048]
    f, b = paged_attention.work(lens, 16, 16, 64)
    # q.k and p.v: 2 flops per multiply-add, every head, every key
    assert f == 2 * 2 * 16 * 64 * sum(lens)
    # K and V of each actual token (no padding to 16-token blocks), bf16,
    # plus q and the output of each sequence
    assert b == sum(lens) * 2 * 16 * 64 * 2 + 4 * 2 * 16 * 64 * 2
    # the order of the sequences and how they split does not matter
    assert paged_attention.work(lens[::-1], 16, 16, 64) == (f, b)
    f2, b2 = paged_attention.work([1000, 1366], 16, 16, 64)
    assert f2 == f and b2 - 2 * 2 * 16 * 64 * 2 == b - 4 * 2 * 16 * 64 * 2
    # a quantized pool halves the payload and adds the scales
    fq, bq = paged_attention.work(lens, 16, 16, 64, "int8")
    assert fq == f
    assert bq == sum(lens) * (2 * 16 * 64 + 2 * 16 * 4) + 4 * 2 * 16 * 64 * 2


def test_fused_reduce_rows_counts_the_rows_once_per_launch():
    f, b = fused_reduce_rows.work(8, 151936, ("max", "sum", "sumsq"))
    assert b == 8 * 151936 * 4 + 8 * 3 * 4
    assert f == 8 * 151936 * 4
    two = fused_reduce_rows.logit_stats(8, 100)
    assert len(two) == 2 and two[1] == fused_reduce_rows.work(8, 100)


def test_model_step_flops_of_qwen_shapes():
    w = model_step.layer_weight_macs(1024, 2816, 16, 16, 64)
    assert w == 4 * 1024 * 1024 + 3 * 1024 * 2816
    f = model_step.decode_flops([100, 200], 24, 1024, 2816, 16, 16, 64,
                                151936)
    assert f == 24 * (2 * w * 2 + 4 * 16 * 64 * 300) + 2 * 1024 * 151936 * 2
    p = model_step.prefill_flops(32, 2, 24, 1024, 2816, 16, 16, 64, 151936)
    assert p == 24 * (2 * w * 2 + 4 * 16 * 64 * (33 + 34)) \
        + 2 * 1024 * 151936


def test_roofline_share_takes_the_binding_bound():
    peak = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    # bytes bound: 50 bytes need 5 s; flops need 1 s; took 10 s
    assert views.roofline_pct(100.0, 50.0, 10.0, peak) == pytest.approx(50)
    assert views.roofline_pct(0.0, 0.0, 1.0, peak) is None


def test_peaks_table_names_its_source_and_the_v5e_kind():
    peaks = spec.load_json(spec.BENCH_DIR / "peaks.json")
    v5e = peaks["TPU v5 lite"]
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert "TPU v5e" in v5e["source"]
