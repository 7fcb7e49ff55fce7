"""The trace reduction on a trace recorded on the chip (TPU v5 lite, three
closed-loop sweeps of paper4_shared_log.transient_ect), against the
numbers read off it by hand, and the kernel's logical byte count."""

import gzip
import json
import os

import pytest

from bench import devtrace, reference, spec
from bench.metrics import sched_kernel_roofline

DATA = os.path.join(os.path.dirname(__file__), "data",
                    "trace_shared_log_ect.json.gz")


@pytest.fixture(scope="module")
def summary():
    with gzip.open(DATA, "rt") as fh:
        events = json.load(fh)
    events["devices"] = {int(k): v for k, v in events["devices"].items()}
    return devtrace.reduce(events, n_devices=1)


def test_window_and_busy(summary):
    # window: first host span at 0 ns to the end of the last block span
    assert summary["window_s"] == pytest.approx(0.138205946, abs=1e-12)
    # device ops never overlap on this chip: busy = their sum
    assert summary["busy_s"] == pytest.approx(0.129550212, abs=1e-12)
    idle = 100 * (1 - summary["busy_s"] / summary["window_s"])
    assert idle == pytest.approx(6.262924462, abs=1e-6)


def test_kernel_and_xla_times(summary):
    assert summary["kernel_ns"] == pytest.approx(47190831.0)
    assert summary["kernel_events"] == 3
    assert summary["other_ns"] == pytest.approx(82359381.0)
    assert summary["collective_events"] == 0
    ctx = {"summary": summary, "sweeps": 3}
    ms = spec.metric_reader("sched_kernel_ms").read(ctx)
    assert ms == pytest.approx(15.730277, abs=1e-6)
    assert spec.metric_reader("xla_ops_ms").read(ctx) == pytest.approx(
        27.453127, abs=1e-6)


def test_host_spans_and_breakdown(summary):
    spans = summary["host_spans"]
    assert {k: v["n"] for k, v in spans.items()} == {
        "key": 3, "dispatch": 3, "block": 3}
    assert spans["dispatch"]["total_ns"] == 845269 + 1108590 + 1088860
    assert summary["top_ops"][0] == ["sched_stream_batch.1",
                                     pytest.approx(0.047190831)]
    assert summary["top_ops"][1][0] == "fusion.8"
    assert summary["idle_gaps"][0] == ["block", pytest.approx(0.001642489)]
    assert summary["idle_gaps"][4] == ["key", pytest.approx(0.001281058)]
    assert len(summary["idle_gaps"]) == 10


def test_logical_bytes_at_paper_scale():
    def shape(config):
        return reference.shape_from(
            spec.load_cell(f"{config}.transient_ect").config,
            spec.load_cell("paper4_shared_log.transient_ect").traffic)

    # shared_log: 100 streams of 20 windows x 100 slots, 100 servers
    per_stream = (2000 * 12 + 1600 + 4 + 2000 * 8 + 1600 + 20 * 400 + 20)
    assert sched_kernel_roofline.logical_bytes(
        shape("paper4_shared_log")) == 100 * per_stream + 100 * 20 * 400
    # per_client: 100 x 200 streams of one 10-slot window
    per_stream = (10 * 12 + 1600 + 4 + 10 * 8 + 1600 + 400 + 20)
    assert sched_kernel_roofline.logical_bytes(
        shape("paper4_per_client")) == 20000 * per_stream + 100 * 400


def test_roofline_share_is_bytes_over_bandwidth_over_kernel_time(summary):
    sh = reference.shape_from(
        spec.load_cell("paper4_shared_log.transient_ect").config,
        spec.load_cell("paper4_shared_log.transient_ect").traffic)
    ctx = {"summary": summary, "sweeps": 3, "shape": sh,
           "peaks": spec.peaks("TPU v5 lite")}
    pct = spec.metric_reader("sched_kernel_roofline").read(ctx)
    least = sched_kernel_roofline.logical_bytes(sh) / 819e9
    assert pct == pytest.approx(100 * least / 0.015730277)
    assert 0 < pct < 1


def test_collectives_are_read_apart_on_the_busiest_chip(summary):
    # the one-chip trace holds no collective: nothing to read
    ctx = {"summary": summary, "sweeps": 3}
    assert spec.metric_reader("collective_ms").read(ctx) is None
    # two chips, two sweeps: chip 1 spends most on collectives
    events = {"host": [["dispatch", 0, 10], ["block", 10, 990]],
              "devices": {
                  0: [["sched_stream_grid.1", 100, 300],
                      ["all-gather.3", 400, 50], ["fusion.2", 500, 100]],
                  1: [["sched_stream_grid.1", 100, 300],
                      ["all-reduce.1", 400, 120],
                      ["collective-permute.2", 600, 80]]}}
    s = devtrace.reduce(events, n_devices=2)
    assert s["collective_events"] == 3
    assert s["collective_ns_max"] == 200
    assert s["kernel_ns"] == 300 and s["other_ns"] == 50
    ctx = {"summary": s, "sweeps": 2}
    assert spec.metric_reader("collective_ms").read(ctx) == pytest.approx(
        100 / 1e6)
    assert spec.metric_reader("xla_ops_ms").read(ctx) == pytest.approx(
        25 / 1e6)
