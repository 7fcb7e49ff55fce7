"""The cells of one Spider II namespace and of round-robin: they load at
their size, and the program agrees with the reference at a small cut."""

import pytest

from bench import reference, run, spec
from bench.tests.test_bench_harness import TINY, drive, tiny_cell


@pytest.mark.parametrize("name,servers,window", [
    ("spider2_1008_shared_log.transient_mlml", 1008, 1008),
    ("paper4_shared_log.transient_rr", 100, 100)])
def test_the_new_cells_load_at_their_size(name, servers, window):
    cell = spec.load_cell(name)
    sh = reference.shape_from(cell.config, cell.traffic)
    assert (sh.n_servers, sh.n_windows, sh.window_size) == (servers, 20,
                                                            window)
    assert sh.streams == (1, 20 * servers, window)
    assert callable(reference.load_policy(sh.policy).choose)


@pytest.mark.parametrize("name,sim", [
    ("spider2_1008_shared_log.transient_mlml",
     dict(n_servers=160, n_requests=640, n_trials=4, window_size=160,
          n_clients=320)),
    ("paper4_shared_log.transient_rr", TINY)])
def test_new_cells_program_at_a_small_cut_is_correct(name, sim):
    """160 servers and windows of 160 put two 128-lane blocks on both
    axes of MLML's window plan."""
    cell = tiny_cell(name, **sim)
    res = drive(cell, run.program_sweep(cell), seconds=0.3)
    assert res["correct"], res
    assert res["compared"]["wrong_answers"]["value"] == 0.0
