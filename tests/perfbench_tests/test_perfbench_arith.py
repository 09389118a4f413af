"""Metric arithmetic: bus bytes, sum of bytes over sum of time, and
percentiles over every call; the readers of the end-to-end metrics; the
peak table."""

from __future__ import annotations

import statistics

import pytest

from perfbench import arith, harness, peaks


def test_allreduce_bus_bytes_is_the_ring_convention():
    assert arith.allreduce_bus_bytes(1 << 30, 4) == 1.5 * (1 << 30)
    assert arith.allreduce_bus_bytes(8, 2) == 8.0
    assert arith.allreduce_bus_bytes(1024, 1) == 0.0


def test_rate_is_sum_of_bytes_over_sum_of_time():
    # one fast small call and one slow large one: not the mean of rates
    nbytes, seconds = [1e6, 1e9], [1e-2, 1.0]
    assert arith.rate(nbytes, seconds) == pytest.approx(1.001e9 / 1.01)
    mean_of_rates = (1e8 + 1e9) / 2
    assert arith.rate(nbytes, seconds) != pytest.approx(mean_of_rates)
    with pytest.raises(ValueError):
        arith.rate([1.0], [0.0])


def test_percentile_is_nearest_rank_over_all_values():
    values = list(range(1, 101))
    assert arith.percentile(values, 0.99) == 99
    assert arith.percentile(values, 0.50) == 50
    assert arith.percentile(values, 1.0) == 100
    assert arith.percentile([7.0], 0.99) == 7.0
    # two chunks whose own p99s average to something no call took
    a, b = [1.0] * 99 + [100.0], [2.0] * 100
    chunked = (arith.percentile(a, 0.99) + arith.percentile(b, 0.99)) / 2
    assert chunked == 1.5
    assert arith.percentile(a + b, 0.99) == 2.0
    with pytest.raises(ValueError):
        arith.percentile([], 0.5)


def test_spread_uses_statistics_quartiles():
    values = [10.0, 11.0, 9.0, 10.5, 9.5, 10.2]
    q1, _, q3 = statistics.quantiles(values, n=4)
    assert arith.spread(values) == pytest.approx(
        (q3 - q1) / statistics.median(values))


def _calls(rows):
    calls = harness.Calls()
    for row in rows:
        calls.add(*row)
    return calls


def _reading(calls, nranks=4):
    return harness.Reading(nranks=nranks, setup_s=12.5, calls=calls,
                           traces={}, peaks=peaks.peaks_for("TPU v5 lite"))


def test_end_to_end_readers_on_recorded_calls():
    calls = _calls([
        # group, bytes per rank, call, return, ready
        ("lat", 8, 0.0, 0.0001, 0.0002),
        ("lat", 4096, 1.0, 1.0001, 1.0004),
        ("lat", 65536, 2.0, 2.0002, 2.0003),
        ("bw", 1 << 24, 3.0, 3.0001, 3.001),
        ("bw", 1 << 30, 4.0, 4.0001, 4.02),
    ])
    r = _reading(calls)
    read = lambda m: harness.load_module("metrics", m).read(r)
    bus = 1.5 * ((1 << 24) + (1 << 30))
    assert read("busbw_GBps") == pytest.approx(bus / (0.001 + 0.02) / 1e9)
    assert read("lat_p50_us") == pytest.approx(300.0)
    assert read("lat_p50_us.local") == read("lat_p50_us")
    assert read("lat_p99_us") == pytest.approx(400.0)
    assert read("dispatch_us.lat") == pytest.approx(100.0)
    assert read("setup_s") == 12.5
    assert read("reduce_GBps") is None  # no reduce group in these calls


def test_reduce_rate_counts_message_bytes_once():
    calls = _calls([("reduce", 1 << 24, 0.0, 0.0, 0.001),
                    ("reduce", 1 << 30, 1.0, 1.0, 1.009)])
    r = _reading(calls, nranks=1)
    got = harness.load_module("metrics", "reduce_GBps").read(r)
    assert got == pytest.approx(((1 << 24) + (1 << 30)) / 0.010 / 1e9)


def test_peak_table_knows_v5e_and_refuses_an_unknown_kind():
    p = peaks.peaks_for("TPU v5 lite")
    assert (p["hbm_GBps"], p["ici_GBps"], p["bf16_TFLOPs"]) == \
        (819.0, 200.0, 197.0)
    assert "TPU v5e" in p["source"]
    with pytest.raises(peaks.UnknownDevice, match="TPU v9"):
        peaks.peaks_for("TPU v9")
    with pytest.raises(KeyError):
        peaks.peaks_for("cpu")
