"""Benchmark tooling regressions: BENCH_sched.json trajectory rendering,
the sharded series' process layout and the entry points' compile cache.

The trajectory renderer consumes a history file that GROWS its schema
over time (new series appear per PR) and can be empty or half-written
(interrupted emit_bench_point, fresh checkout).  These tests pin the
tolerant behaviour: mixed-schema points render with missing cells, an
empty/corrupt/missing file reports instead of raising.
"""

import json

import pytest

sched_perf = pytest.importorskip("benchmarks.sched_perf")

# A realistic mixed-schema history: run 0 predates the kernel backend
# entirely, run 1 predates kernel_batch_req_s, run 2 has everything
# including the sort-policy series; run 3 is schema junk (not a dict).
MIXED_HISTORY = [
    {"ts": 1700000000.0, "phase_s_rr": 5.0, "phase_s_trh": 3.0,
     "phase_s_ect": 2.5, "transient_p99_trh": 1.2},
    {"ts": 1700000100.0, "phase_s_rr": 5.1, "phase_s_trh": 3.1,
     "phase_s_ect": 2.4, "transient_p99_trh": 1.1,
     "kernel_backend_phase_s": 0.9, "kernel_req_s": 100000.0,
     "engine_req_s": 200000.0, "kernel_bit_exact": True},
    {"ts": 1700000200.0, "phase_s_rr": 5.0, "phase_s_trh": 3.0,
     "phase_s_ect": 2.3, "transient_p99_trh": 1.0,
     "kernel_backend_phase_s": 0.8, "kernel_req_s": 150000.0,
     "engine_req_s": 180000.0, "kernel_batch_req_s": 390000.0,
     "kernel_batch_req_s_mlml": 120000.0,
     "kernel_batch_req_s_nltr": 110000.0, "bench_reps": 3},
    ["schema", "junk"],
]


def test_trajectory_tolerates_mixed_schema(tmp_path, capsys):
    path = tmp_path / "BENCH_sched.json"
    path.write_text(json.dumps(MIXED_HISTORY))
    hist = sched_perf.trajectory(str(path), str(tmp_path / "fig.png"))
    out = capsys.readouterr().out
    assert len(hist) == 3                       # junk point dropped
    assert "perf trajectory (3 runs" in out
    # missing series render as placeholders, present ones as numbers
    assert "—" in out
    assert "390000" in out
    assert "kernel_batch_req_s_mlml" in out


def test_trajectory_empty_file_renders_without_error(tmp_path, capsys):
    """Regression: a zero-byte BENCH_sched.json used to raise
    JSONDecodeError out of trajectory()."""
    path = tmp_path / "BENCH_sched.json"
    path.write_text("")
    assert sched_perf.trajectory(str(path), str(tmp_path / "f.png")) == []
    assert "empty or unreadable" in capsys.readouterr().out


def test_trajectory_corrupt_file_renders_without_error(tmp_path, capsys):
    path = tmp_path / "BENCH_sched.json"
    path.write_text('[{"ts": 17')                # interrupted write
    assert sched_perf.trajectory(str(path), str(tmp_path / "f.png")) == []
    assert "empty or unreadable" in capsys.readouterr().out


def test_trajectory_missing_file_renders_without_error(tmp_path, capsys):
    missing = tmp_path / "nope.json"
    assert sched_perf.trajectory(str(missing),
                                 str(tmp_path / "f.png")) == []
    assert "not found" in capsys.readouterr().out


def test_trajectory_empty_list_renders_without_error(tmp_path, capsys):
    path = tmp_path / "BENCH_sched.json"
    path.write_text("[]")
    assert sched_perf.trajectory(str(path), str(tmp_path / "f.png")) == []
    assert "is empty" in capsys.readouterr().out


# ----------------------------------------------- git-dirty stamping (§16)

def test_stamp_git_warns_loudly_on_dirty_tree(monkeypatch, capsys):
    monkeypatch.setattr(sched_perf, "_git_sha", lambda: "f" * 40)
    monkeypatch.setattr(sched_perf, "_git_dirty", lambda: True)
    point = sched_perf._stamp_git({})
    assert point["git_dirty"] is True
    assert point["git_sha"] == "f" * 40
    err = capsys.readouterr().err
    assert "DIRTY" in err and "regression baseline" in err


def test_stamp_git_silent_on_clean_tree(monkeypatch, capsys):
    monkeypatch.setattr(sched_perf, "_git_sha", lambda: "a" * 40)
    monkeypatch.setattr(sched_perf, "_git_dirty", lambda: False)
    point = sched_perf._stamp_git({})
    assert point["git_dirty"] is False
    assert capsys.readouterr().err == ""


def test_trajectory_renders_dirty_marker_column(tmp_path, capsys):
    """Points stamped git_dirty render a D in the dirty column; clean
    points a ·; pre-stamp points a ?."""
    history = [
        {"ts": 1700000000.0, "phase_s_rr": 5.0},                 # pre-stamp
        {"ts": 1700000100.0, "phase_s_rr": 5.0, "git_dirty": True},
        {"ts": 1700000200.0, "phase_s_rr": 5.0, "git_dirty": False},
    ]
    path = tmp_path / "BENCH_sched.json"
    path.write_text(json.dumps(history))
    sched_perf.trajectory(str(path), str(tmp_path / "f.png"))
    out = capsys.readouterr().out
    lines = [ln for ln in out.splitlines() if ln.strip()]
    # tmp_path embeds this test's name, so the banner line (which prints
    # the json path) also contains "dirty" — key on the header shape
    header = next(ln for ln in lines if "when" in ln and "dirty" in ln)
    col = header.index("dirty") + len("dirty") - 1
    rows = lines[lines.index(header) + 1:lines.index(header) + 4]
    assert [row[col] for row in rows] == ["?", "D", "·"]


def test_latest_bench_point_carries_dirty_stamp():
    """The shipped BENCH_sched.json's newest point must carry the
    git_dirty stamp — the marker the regression gate and the trajectory
    column both key on."""
    import os
    if not os.path.exists(sched_perf.BENCH_PATH):
        pytest.skip("no BENCH_sched.json in this checkout")
    with open(sched_perf.BENCH_PATH) as f:
        history = json.load(f)
    assert isinstance(history, list) and history
    latest = history[-1]
    assert isinstance(latest.get("git_dirty"), bool)
    assert isinstance(latest.get("git_sha"), str)


# ------------------------------------------- regression gate (run.py)

run_mod = pytest.importorskip("benchmarks.run")

CLEAN_BASE = {"ts": 1.0, "git_sha": "b" * 40, "git_dirty": False,
              "kernel_req_s": 100000.0, "kernel_batch_req_s": 400000.0,
              "sharded_req_s_8d": 300000.0}


def test_check_regression_passes_within_tolerance(tmp_path, capsys):
    latest = {"ts": 2.0, "git_sha": "c" * 40, "git_dirty": False,
              "kernel_req_s": 90000.0, "kernel_batch_req_s": 395000.0,
              "sharded_req_s_8d": 290000.0}
    p = tmp_path / "BENCH.json"
    p.write_text(json.dumps([CLEAN_BASE, latest]))
    assert run_mod.check_regression(str(p)) == 0
    assert "ok (3 series)" in capsys.readouterr().out


def test_check_regression_fails_past_tolerance(tmp_path, capsys):
    latest = {"ts": 2.0, "git_sha": "c" * 40,
              "kernel_req_s": 100000.0,
              "kernel_batch_req_s": 100000.0}       # -75%: regressed
    p = tmp_path / "BENCH.json"
    p.write_text(json.dumps([CLEAN_BASE, latest]))
    assert run_mod.check_regression(str(p)) == 1
    out = capsys.readouterr().out
    assert "REGRESSED" in out and "kernel_batch_req_s" in out


def test_check_regression_skips_dirty_baselines(tmp_path, capsys):
    dirty = dict(CLEAN_BASE, git_dirty=True,
                 kernel_batch_req_s=9999999.0)      # tempting but dirty
    latest = {"ts": 3.0, "kernel_batch_req_s": 390000.0}
    p = tmp_path / "BENCH.json"
    p.write_text(json.dumps([CLEAN_BASE, dirty, latest]))
    assert run_mod.check_regression(str(p)) == 0    # vs CLEAN_BASE, not dirty
    assert "ok" in capsys.readouterr().out


def test_check_regression_trivial_passes(tmp_path, capsys):
    p = tmp_path / "BENCH.json"
    assert run_mod.check_regression(str(p)) == 0            # missing
    p.write_text(json.dumps([CLEAN_BASE]))
    assert run_mod.check_regression(str(p)) == 0            # one point
    p.write_text(json.dumps([{"ts": 1.0, "git_dirty": True},
                             {"ts": 2.0}]))
    assert run_mod.check_regression(str(p)) == 0            # no clean base
    capsys.readouterr()


def test_sharded_sweep_runs_in_process_off_forced_cpu(monkeypatch, capsys):
    """Off the forced-host-device CPU setup (as on a chip) the sharded
    series runs in THIS process over sub-meshes, measures only the
    device counts dividing jax.device_count(), starts no child process,
    and names the device it ran on."""
    import jax

    def no_child(*a, **k):
        raise AssertionError("the sharded series started a child process")

    monkeypatch.setattr(sched_perf, "_forced_host_devices", lambda: False)
    monkeypatch.setattr(sched_perf.subprocess, "run", no_child)
    n = jax.device_count()
    out = sched_perf.sharded_sweep_throughput.__wrapped__(
        n_servers=8, n_requests=32, window_size=8, n_trials=4, reps=1,
        devices=(1, 2, 3))
    assert out["devices"] == [d for d in (1, 2, 3) if n % d == 0]
    assert out["platform"] == jax.devices()[0].platform
    assert out["device_kind"] == jax.devices()[0].device_kind
    assert out["sharded_bit_exact"] and out["sharded_cross_backend_exact"]
    assert "sharded sweep throughput" in capsys.readouterr().out


@pytest.mark.parametrize("env_dir", [None, "from-env"])
def test_compile_cache_dir_is_env_or_fixed_checkout_path(monkeypatch,
                                                          tmp_path, env_dir):
    """The entry points' compile cache: JAX_COMPILATION_CACHE_DIR when set
    (and then no other directory is configured), else <repo>/.jax_cache."""
    import os

    import jax

    from repro.launch import compile_cache

    before = jax.config.jax_compilation_cache_dir
    if env_dir is None:
        monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
        want = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), ".jax_cache")
    else:
        want = str(tmp_path / env_dir)
        monkeypatch.setenv(compile_cache.ENV_VAR, want)
    try:
        assert compile_cache.enable_compile_cache() == want
        if env_dir is None:
            assert jax.config.jax_compilation_cache_dir == want
        else:
            assert jax.config.jax_compilation_cache_dir == before
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
