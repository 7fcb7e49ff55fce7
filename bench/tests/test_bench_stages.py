"""The stage split of the device trace (``bench/stagetrace.py``): scope
paths to stages, a synthetic two-chip trace with every kind of op and
gap, and a trace recorded on the chip (TPU v5 lite, three closed-loop
sweeps of paper4_per_client.transient_ect), against numbers read off
them by hand."""

import gzip
import json
import os

import pytest

from bench import devtrace, spec, stagetrace

DATA = os.path.join(os.path.dirname(__file__), "data",
                    "trace_per_client_ect_stages.json.gz")
READERS = ("prep_ms", "engine_prep_ms", "book_ms", "merge_ms", "post_ms")


@pytest.mark.parametrize("path,stage", [
    ("jit(run_trials)/prep/vmap(jit(_uniform))/add", "prep"),
    ("jit(run_trials)/sched/engine_prep/vmap(vmap())/sort", "engine_prep"),
    ("jit(run_trials)/sched/kernel/jit(sched_stream_grid)/pad", "kernel"),
    ("jit(run_trials)/sched/kernel/jit(sched_stream_grid)/merge/"
     "vmap(jit(masked_client_mean))/add", "merge"),
    ("jit(run_trials)/sched/vmap(vmap(book))/gather", "book"),
    ("jit(run_trials)/post/vmap()/reduce_sum", "post"),
    ("jit(run_trials)/sched/slice", "sched_other"),
    ("jit(fold_in)/jit(_threefry_fold_in)/threefry2x32", "unscoped"),
    ("", "unscoped"),
    # a primitive or function that merely contains a stage's name
    ("jit(run_trials)/jit(prepare)/bookkeeping", "unscoped"),
])
def test_stage_of_a_scope_path(path, stage):
    assert stagetrace.stage_of(path) == stage


P = "jit(run_trials)/"
# two chips, one sweep each: chip 0 holds every kind of op, a gap inside
# the program and one between programs; chip 1 the same program shorter
EVENTS = {
    "host": [["key", 0, 40], ["dispatch", 40, 50], ["block", 90, 430],
             ["key", 600, 30]],
    "host_events": [["PjitFunction(run_trials)", 45, 40],
                    ["pjit_call_impl", 50, 30],
                    ["PjitFunction(run_trials)", 46, 4]],
    "modules": {0: [["jit_run_trials", 100, 420],
                    ["jit_fold_in", 600, 20]],
                1: [["jit_run_trials", 100, 300]]},
    "devices": {
        0: [["fusion.1", 100, 50, P + "prep/vmap(jit(_uniform))/add"],
            ["sort.2", 150, 30, P + "sched/engine_prep/vmap(vmap())/sort"],
            ["sched_stream_grid.1", 180, 100,
             P + "sched/kernel/jit(sched_stream_grid)/pallas_call"],
            ["fusion.3", 280, 10,
             P + "sched/kernel/jit(sched_stream_grid)/slice"],
            ["fusion.4", 290, 20,
             P + "sched/kernel/jit(sched_stream_grid)/merge/vmap()/add"],
            ["all-gather.1", 310, 15, P + "sched/shard_map/merge/all_gather"],
            ["fusion.5", 325, 5, P + "sched/slice"],
            # 330..400: idle inside the program
            ["fusion.6", 400, 40, P + "sched/vmap(vmap(book))/gather"],
            ["copy-start.3", 440, 5, ""],
            ["fusion.7", 445, 75, P + "post/vmap()/reduce_sum"],
            # 520..600: idle between the programs
            ["fusion", 600, 20, "jit(fold_in)/threefry2x32"]],
        1: [["fusion.1", 100, 40, P + "prep/add"],
            ["sched_stream_grid.1", 140, 100,
             P + "sched/kernel/jit(sched_stream_grid)/pallas_call"],
            ["fusion.4", 240, 30, P + "sched/kernel/merge/add"],
            ["all-reduce.2", 270, 20, P + "sched/merge/psum"],
            ["fusion.6", 290, 110, P + "sched/book/gather"]],
        # a third chip beyond the cell's two is left out
        2: [["fusion.1", 100, 10**6, P + "prep/add"]]},
}


@pytest.fixture(scope="module")
def synthetic():
    return stagetrace.reduce(EVENTS, n_devices=2)


def test_stages_partition_the_xla_time(synthetic):
    s = synthetic
    assert s["n_devices"] == 2 and s["sweeps"] == 1
    # means over the two chips: the kernel and the collectives left out
    assert s["stage_ns"] == {"prep": 45, "engine_prep": 15, "kernel": 5,
                             "merge": 25, "sched_other": 2.5, "book": 75,
                             "unscoped": 12.5, "post": 37.5}
    assert s["stage_events"] == {"prep": 2, "engine_prep": 1, "kernel": 1,
                                 "merge": 2, "sched_other": 1, "book": 2,
                                 "unscoped": 2, "post": 1}
    # the same trace through the harness's reduction: the stages sum to
    # what xla_ops_ms reads
    plain = dict(EVENTS, devices={d: [ev[:3] for ev in evs]
                                  for d, evs in EVENTS["devices"].items()})
    other = devtrace.reduce(plain, n_devices=2)["other_ns"]
    assert sum(s["stage_ns"].values()) == pytest.approx(other)
    assert s["xla_ns"] == pytest.approx(other)


def test_gap_sites(synthetic):
    gaps = synthetic["gap_sites"]
    # chip 0 only: 0..100 before the first op, 330..400 inside the
    # program, 520..600 between programs, 620..630 after the last op
    assert [(g["ms"], g["at_ms"]) for g in gaps] == [
        (100 / 1e6, 0.0), (80 / 1e6, 520 / 1e6), (70 / 1e6, 330 / 1e6),
        (10 / 1e6, 620 / 1e6)]
    edge, between, inside, end = gaps
    assert end["before"] == ["unscoped", "fusion"] and end["after"] is None
    assert edge["before"] is None
    assert edge["after"] == ["prep", "fusion.1"]
    assert edge["same_program"] is False
    assert inside["before"] == ["sched_other", "fusion.5"]
    assert inside["host"] == "block"
    assert inside["after"] == ["book", "fusion.6"]
    assert inside["same_program"] is True
    assert between["before"] == ["post", "fusion.7"]
    assert between["after"] == ["unscoped", "fusion"]
    assert between["same_program"] is False
    assert between["host"] == "between spans"


def test_dispatch_host_events(synthetic):
    assert synthetic["dispatch_ms"] == pytest.approx(50 / 1e6)
    # the PjitFunction nested in another counts once
    assert synthetic["dispatch_events"] == [
        ["PjitFunction(run_trials)", pytest.approx(40 / 1e6)],
        ["pjit_call_impl", pytest.approx(30 / 1e6)]]


def test_nested_host_events_stay_on_their_span():
    events = [["dispatch", 100, 50], ["PjitFunction(f)", 110, 20],
              ["late", 140, 20], ["before", 90, 5], ["dispatch", 200, 10],
              ["inner", 201, 9]]
    spans = [e for e in events if e[0] == "dispatch"]
    assert stagetrace._nested(events, spans) == [
        ["PjitFunction(f)", 110, 20], ["inner", 201, 9]]


HLO = """HloModule jit_run_trials, entry_computation_layout={()->f32[]}

%region_0.5 (a: s32[], b: s32[]) -> s32[] {
  %a = s32[] parameter(0)
  %b = s32[] parameter(1)
  ROOT %add.6 = s32[] add(%a, %b), metadata={op_name="jit(run_trials)/post/vmap()/add" stack_frame_id=4}
}

%fused_computation.2 (param_0.1: s32[8], param_1.1: s32[8]) -> s32[8] {
  %param_0.1 = s32[8]{0} parameter(0)
  %param_1.1 = s32[8]{0} parameter(1)
  %reshape.9 = s32[8,1]{1,0} reshape(%param_1.1)
  ROOT %scatter.4 = s32[8]{0} scatter(%param_0.1, %reshape.9, %param_1.1), to_apply=%region_0.5
}

%fused_computation.1 (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  ROOT %add.3 = f32[8]{0} add(%param_0, %param_0), metadata={op_name="jit(run_trials)/post/add" stack_frame_id=3}
}

ENTRY %main.9 () -> f32[] {
  %fusion.1 = f32[8]{0} fusion(%constant.2), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(run_trials)/prep/vmap()/mul" stack_frame_id=1}
  %fusion.7 = s32[8]{0} fusion(%iota.1, %iota.1), kind=kCustom, calls=%fused_computation.2
  %copy-start = (f32[8]{0}, f32[8]{0}, u32[]) copy-start(%fusion.1)
  ROOT %sched_stream_batch.1 = f32[] custom-call(%fusion.1), custom_call_target="tpu_custom_call", metadata={op_name="jit(run_trials)/sched/kernel/jit(sched_stream_batch)/pallas_call" stack_frame_id=7}
}
"""


def test_scope_paths_come_from_the_compiled_program():
    module, names = stagetrace.program_op_names(HLO)
    assert module == "jit_run_trials"
    kernel = "jit(run_trials)/sched/kernel/jit(sched_stream_batch)/pallas_call"
    assert {k: v for k, v in names.items() if v} == {
        "add.6": "jit(run_trials)/post/vmap()/add",
        "add.3": "jit(run_trials)/post/add",
        "fusion.1": "jit(run_trials)/prep/vmap()/mul",
        # a scatter fusion XLA built with no op_name: its reducer's
        "scatter.4": "jit(run_trials)/post/vmap()/add",
        "fusion.7": "jit(run_trials)/post/vmap()/add",
        "sched_stream_batch.1": kernel}
    assert names["copy-start"] == "" and names["reshape.9"] == ""
    # only ops inside an execution of that program take its paths: the
    # key's fold_in program has a fusion.1 of its own
    runs = [["jit_run_trials(7)", 100, 100], ["jit_fold_in(3)", 300, 50]]
    ops = [["fusion.1", 100, 10], ["copy-start", 110, 5],
           ["sched_stream_batch.1", 120, 70], ["fusion.1", 310, 20],
           ["fusion.1", 250, 10]]
    assert [ev[3] for ev in stagetrace._scoped(ops, runs, module, names)] \
        == ["jit(run_trials)/prep/vmap()/mul", "", kernel, "", ""]
    # no program text: every op unscoped
    assert [ev[3] for ev in stagetrace._scoped(ops, runs, "", {})] == [""] * 5


def test_readers_give_ms_per_sweep(synthetic, monkeypatch):
    monkeypatch.setattr(stagetrace, "summary", lambda ctx: synthetic)
    got = {r: spec.metric_reader(r).read({}) for r in READERS}
    assert got == {"prep_ms": pytest.approx(45 / 1e6),
                   "engine_prep_ms": pytest.approx(15 / 1e6),
                   "book_ms": pytest.approx(75 / 1e6),
                   "merge_ms": pytest.approx(25 / 1e6),
                   "post_ms": pytest.approx(37.5 / 1e6)}


def test_a_trace_without_scopes_reads_nothing(monkeypatch):
    # the program before it named its stages: every op is unscoped
    bare = dict(EVENTS, devices={d: [ev[:3] for ev in evs]
                                 for d, evs in EVENTS["devices"].items()})
    s = stagetrace.reduce(bare, n_devices=2)
    assert set(s["stage_ns"]) == {"unscoped"}
    monkeypatch.setattr(stagetrace, "summary", lambda ctx: s)
    assert all(spec.metric_reader(r).read({}) is None for r in READERS)


def test_benchmark_lists_the_stage_metrics():
    with open(os.path.join(spec.ROOT, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    per_layer = {m["name"]: m for m in bench["per_layer"]}
    layer = per_layer["xla_ops_ms"]["layer"]
    for r in READERS:
        m = per_layer[r]
        assert (m["source"], m["layer"], m["moves"], m["unit"]) == (
            "device_trace", layer, "decisions_per_s", "ms")
    assert per_layer["merge_ms"]["workloads"] == [
        "paper4_per_client.transient_ect",
        "paper4_per_client_2x2.transient_ect"]


@pytest.fixture(scope="module")
def recorded():
    with gzip.open(DATA, "rt") as fh:
        events = json.load(fh)
    for part in ("devices", "modules"):
        events[part] = {int(k): v for k, v in events[part].items()}
    return events


def test_recorded_trace_splits_xla_ops_ms_by_stage(recorded, monkeypatch):
    s = stagetrace.reduce(recorded, n_devices=1)
    assert s["sweeps"] == 3
    assert s["stage_ns"] == {
        "prep": 557898.0, "engine_prep": 54295922.0, "kernel": 831738.0,
        "merge": 95991.0, "book": 20511360.0, "post": 11477612.0,
        "unscoped": 290242.0}
    assert s["stage_events"] == {
        "prep": 258, "engine_prep": 213, "kernel": 75, "merge": 147,
        "book": 51, "post": 492, "unscoped": 473}
    # the split is whole: it sums to what xla_ops_ms reads on this trace,
    # and under 1% of it is outside every stage
    plain = dict(recorded, devices={0: [ev[:3] for ev in
                                        recorded["devices"][0]]})
    summary = devtrace.reduce(plain, n_devices=1)
    assert s["xla_ns"] == summary["other_ns"] == 88060763.0
    assert spec.metric_reader("xla_ops_ms").read(
        {"summary": summary, "sweeps": 3}) == pytest.approx(29.353588,
                                                            abs=1e-6)
    assert s["stage_ns"]["unscoped"] / s["xla_ns"] < 0.01
    monkeypatch.setattr(stagetrace, "summary", lambda ctx: s)
    got = {r: spec.metric_reader(r).read({}) for r in READERS}
    assert got == {"prep_ms": pytest.approx(0.185966, abs=1e-6),
                   "engine_prep_ms": pytest.approx(18.098641, abs=1e-6),
                   "book_ms": pytest.approx(6.837120, abs=1e-6),
                   "merge_ms": pytest.approx(0.031997, abs=1e-6),
                   "post_ms": pytest.approx(3.825871, abs=1e-6)}


def test_recorded_trace_gaps_lie_between_programs(recorded):
    s = stagetrace.reduce(recorded, n_devices=1)
    first = s["gap_sites"][0]
    # the device waits for the host's next key: from the last op of the
    # sweep's post stage to the first op (a copy) of the next program
    assert first == {"ms": pytest.approx(1.632988), "at_ms": pytest.approx(
        35.432864), "host": "key", "before": ["post", "broadcast_in_dim.375"],
        "after": ["unscoped", "copy-start"], "same_program": False}
    # then for the dispatch of run_trials after the key's fold_in
    assert s["gap_sites"][2]["host"] == "dispatch"
    assert s["gap_sites"][2]["before"] == ["unscoped", "pad_add_fusion"]
    # inside a program the device never waits more than 4 microseconds
    assert max(g["ms"] for g in s["gap_sites"] if g["same_program"]) < 0.004
    assert s["dispatch_events"][0] == ["PjitFunction(run_trials)",
                                       pytest.approx(1.0979)]
