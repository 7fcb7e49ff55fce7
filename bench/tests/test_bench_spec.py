"""Cells, configurations, traffic mixes and per-layer metrics are found
by name: adding one adds files and edits none."""

import json
import os
import shutil

import pytest

from bench import spec


def test_every_cell_of_the_benchmark_loads():
    with open(os.path.join(spec.ROOT, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    for w in bench["workloads"]:
        cell = spec.load_cell(w["name"])
        assert cell.config["name"] == w["config"]
        assert cell.traffic["name"] == w["traffic"]
        assert set(cell.limits) == {"wrong_answers", "value_gap"}
        assert cell.end_to_end and cell.per_layer
    for m in bench["per_layer"]:
        assert callable(spec.metric_reader(m["name"]).read)


@pytest.fixture
def tree(tmp_path):
    """A copy of the benchmark's files, to add to."""
    here = tmp_path / "bench"
    shutil.copytree(spec.HERE, here,
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    shutil.copy(os.path.join(spec.ROOT, "BENCHMARK.json"), tmp_path)
    return tmp_path, here


def test_new_config_traffic_cell_and_metric_are_found_by_name(tree):
    root, here = tree
    with open(here / "configs" / "paper4_shared_log.json") as fh:
        config = json.load(fh)
    config["name"] = "paper4_shared_log_1000t"
    config["sim"]["n_trials"] = 1000
    (here / "configs" / "paper4_shared_log_1000t.json").write_text(
        json.dumps(config))
    with open(here / "traffic" / "transient_ect.json") as fh:
        traffic = json.load(fh)
    traffic["name"] = "transient_rr"
    traffic["policy"] = {"name": "rr", "threshold": 0.0, "rng": "lcg"}
    (here / "traffic" / "transient_rr.json").write_text(json.dumps(traffic))
    (here / "limits" / "paper4_shared_log_1000t.transient_rr.json"
     ).write_text('{"wrong_answers": 0.001, "value_gap": 0.0001}')
    (here / "metrics" / "probe_count.py").write_text(
        "def read(ctx):\n    n = ctx.get('probes')\n"
        "    return None if n is None else float(n)\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["workloads"].append({
        "name": "paper4_shared_log_1000t.transient_rr",
        "config": "paper4_shared_log_1000t", "traffic": "transient_rr",
        "chips": 1, "why": "test"})
    bench["per_layer"].append({
        "name": "probe_count", "unit": "n", "better": "higher",
        "source": "program_counter", "layer": "device",
        "moves": "decisions_per_s",
        "workloads": ["paper4_shared_log_1000t.transient_rr"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = spec.load_cell("paper4_shared_log_1000t.transient_rr",
                          root=str(root), here=str(here))
    assert cell.config["sim"]["n_trials"] == 1000
    assert cell.traffic["policy"]["name"] == "rr"
    assert "probe_count" in [m["name"] for m in cell.per_layer]
    reader = spec.metric_reader("probe_count", here=str(here))
    assert reader.read({"probes": 7}) == 7.0
    # every cell asks every reader; where it finds nothing to read it
    # returns None and the harness leaves the metric out
    other = spec.load_cell("paper4_shared_log.transient_ect",
                           root=str(root), here=str(here))
    assert "probe_count" in [m["name"] for m in other.per_layer]
    assert reader.read({"sweeps": 7}) is None


def test_missing_files_are_named(tree):
    root, here = tree
    os.remove(here / "traffic" / "transient_mlml.json")
    with pytest.raises(LookupError, match="traffic"):
        spec.load_cell("paper4_shared_log.transient_mlml",
                       root=str(root), here=str(here))
    with pytest.raises(LookupError, match="nope"):
        spec.metric_reader("nope", here=str(here))


def test_a_device_kind_missing_from_the_peaks_is_an_error():
    assert spec.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(LookupError):
        spec.peaks("TPU v99")
