"""DESIGN.md §16 lowering-autotuner tests.

Four groups: the on-disk table's failure modes (missing / corrupt /
stale-version caches degrade to the static defaults, never raise), the
tile resolution modes (default / fused / tuned with explicit-parameter
precedence), the tuner's determinism under an injected timer (same
table bytes twice), and the association property — a tuned tile shape
must leave every backend bitwise identical to the others (kernel ==
jax engine == lax.map sequential oracle), for all six kernel policies,
including the cross-backend client-tile fallback that keeps a jax twin
on the SAME merge grouping as the kernel entry it shadows.
"""

import dataclasses
import json

import jax
import numpy as np
import pytest

from repro.core import policy_core, simulate
from repro.core.engine import KERNEL_POLICIES
from repro.core.policies import PolicyConfig
from repro.core.policy_core import (DEFAULT_CLIENT_TILE,
                                    DEFAULT_TRIAL_TILE,
                                    FUSED_SUBLANE_BUDGET,
                                    resolve_grid_tiles)
from repro.tune import autotune, table


def _key(policy="ect", backend="kernel", t=6, c=1, form="batch"):
    return table.config_key(policy=policy, backend=backend, n_servers=8,
                            n_requests=32, n_clients=c, n_trials=t,
                            window_size=8, form=form)


def _cfg(**kw):
    base = dict(n_servers=8, n_requests=32, n_trials=6, window_size=8,
                backend="kernel")
    base.update(kw)
    return simulate.SimConfig(**base)


# ------------------------------------------------------------ table cache

def test_load_table_missing_file_is_empty(monkeypatch, tmp_path):
    monkeypatch.setenv("SCHED_TUNE_PATH", str(tmp_path / "nope.json"))
    assert table.load_table() == {}


def test_load_table_corrupt_file_is_empty(monkeypatch, tmp_path):
    p = tmp_path / "TUNE.json"
    p.write_text('{"version": 1, "entries": {')      # interrupted write
    monkeypatch.setenv("SCHED_TUNE_PATH", str(p))
    assert table.load_table() == {}


def test_load_table_stale_version_is_empty(monkeypatch, tmp_path):
    p = tmp_path / "TUNE.json"
    p.write_text(json.dumps({"version": table.TABLE_VERSION + 1,
                             "entries": {_key(): {"trial_tile": 4,
                                                  "client_tile": 1}}}))
    monkeypatch.setenv("SCHED_TUNE_PATH", str(p))
    assert table.load_table() == {}


def test_load_table_wrong_schema_is_empty(monkeypatch, tmp_path):
    p = tmp_path / "TUNE.json"
    p.write_text(json.dumps(["not", "a", "table"]))
    monkeypatch.setenv("SCHED_TUNE_PATH", str(p))
    assert table.load_table() == {}


def test_store_roundtrip_and_backend_fallback(monkeypatch, tmp_path):
    monkeypatch.setenv("SCHED_TUNE_PATH", str(tmp_path / "TUNE.json"))
    entry = {"trial_tile": 6, "client_tile": 1, "sched_s": 0.5,
             "req_s": 384.0}
    table.store(_key(backend="kernel"), entry)
    assert table.load_table()[_key(backend="kernel")] == entry
    # a jax-backend lookup falls back to the canonical kernel entry, so
    # the engine twin resolves the SAME tiles as the kernel it shadows
    kw = dict(n_servers=8, n_requests=32, n_clients=1, n_trials=6,
              window_size=8)
    assert table.lookup(policy="ect", backend="jax", **kw) == entry
    assert table.lookup(policy="trh", backend="kernel", **kw) is None


def test_resolve_sim_tiles_tuned_miss_degrades_to_fused(monkeypatch,
                                                        tmp_path):
    monkeypatch.setenv("SCHED_TUNE_PATH", str(tmp_path / "empty.json"))
    kw = dict(policy="ect", backend="kernel", n_servers=8, n_requests=32,
              n_clients=1, n_trials=200, window_size=8)
    tuned = table.resolve_sim_tiles(mode="tuned", **kw)
    fused = table.resolve_sim_tiles(mode="fused", **kw)
    assert tuned == fused
    # a populated cache takes over, clamped through the resolvers
    table.store(table.config_key(form="batch", device_count=1, **kw),
                {"trial_tile": 999, "client_tile": 1})
    assert table.resolve_sim_tiles(mode="tuned", **kw) == (200, 1)


def test_resolve_sim_tiles_explicit_params_win(monkeypatch, tmp_path):
    monkeypatch.setenv("SCHED_TUNE_PATH", str(tmp_path / "TUNE.json"))
    kw = dict(policy="ect", backend="kernel", n_servers=8, n_requests=32,
              n_clients=1, n_trials=200, window_size=8)
    table.store(table.config_key(form="batch", device_count=1, **kw),
                {"trial_tile": 64, "client_tile": 1})
    assert table.resolve_sim_tiles(mode="tuned", trial_tile=16, **kw) \
        == (16, 1)


# ------------------------------------------------------- fused resolver

def test_resolve_grid_tiles_deepens_small_client_blocks():
    # 4 clients leave 28 of 32 sublanes idle at the static default —
    # the fused resolver deepens the trial tile to refill the budget
    assert resolve_grid_tiles(100, 4) == (FUSED_SUBLANE_BUDGET // 4, 4)
    # wide client blocks keep the static defaults untouched
    assert resolve_grid_tiles(100, 200) == (DEFAULT_TRIAL_TILE,
                                            DEFAULT_CLIENT_TILE)
    # explicit tiles pass through the static resolvers unchanged
    assert resolve_grid_tiles(100, 4, trial_tile=8, client_tile=2) == (8, 2)
    # clamps still apply: tiny instances never exceed their extents
    assert resolve_grid_tiles(3, 2) == (3, 2)


# ------------------------------------------------- row-depth trial tile

# (policy, trials, servers, requests, window, devices, explicit tile,
#  form, clients) -> (trial tile, programs per device): the benchmark
# cells' shapes, resolved where `simulate._sched_trials` resolves them
PAPER4 = (100, 100, 2000, 100)
SPIDER2 = (32, 1008, 20160, 1008)
ROW_DEPTH_CASES = {
    "paper4_shared_log.ect": (("ect",) + PAPER4 + (1, None, "batch", 1),
                              (50, 2)),
    "paper4_shared_log.rr": (("rr",) + PAPER4 + (1, None, "batch", 1),
                             (50, 2)),
    "paper4_shared_log.mlml": (("mlml",) + PAPER4 + (1, None, "batch", 1),
                               (25, 4)),
    "spider2_1008_shared_log.mlml": (
        ("mlml",) + SPIDER2 + (1, None, "batch", 1), (8, 4)),
    "few_trials": (("ect", 5, 100, 2000, 100, 1, None, "batch", 1), (5, 1)),
    "eight_trials": (("mlml", 8, 100, 2000, 100, 1, None, "batch", 1),
                     (8, 1)),
    "trials_mesh_of_4": (("ect",) + PAPER4 + (4, None, "batch", 1),
                         (25, 1)),
    "explicit_tile_wins": (("ect",) + PAPER4 + (1, 8, "batch", 1), (8, 13)),
    "paper4_per_client.grid": (("ect",) + PAPER4 + (1, None, "grid", 200),
                               (8, 13)),
    # rows of 3 to 7 vregs, 20 windows a stream: the depth is a whole
    # number of sublane tiles, never below the default
    "m300_long.ect": (("ect", 100, 300, 6000, 300, 1, None, "batch", 1),
                      (15, 7)),
    "m600_long.mlml": (("mlml", 100, 600, 12000, 600, 1, None, "batch", 1),
                       (8, 13)),
    "m700_long.ect": (("ect", 100, 700, 14000, 700, 1, None, "batch", 1),
                      (8, 13)),
    "m800_long.ect": (("ect", 100, 800, 16000, 800, 1, None, "batch", 1),
                      (8, 13)),
}


@pytest.mark.parametrize("case", ROW_DEPTH_CASES.values(),
                         ids=ROW_DEPTH_CASES.keys())
def test_trial_tile_follows_row_depth(case):
    """The trial grid's default tile is the deepest within the row's vreg
    budget and the scoped VMEM, balanced over its programs, per device
    (DESIGN.md §20); an explicit tile wins, and the per-client grid keeps
    its tiles."""
    (policy, t, m, r, window, devices, tile, form, c), want = case
    tt, ct = table.resolve_sim_tiles(
        mode="default", policy=policy, backend="kernel", n_servers=m,
        n_requests=r, n_clients=c, n_trials=t, window_size=window,
        device_count=devices, form=form, trial_tile=tile)
    per = -(-t // devices)
    assert (tt, -(-per // tt)) == want
    assert tt >= min(DEFAULT_TRIAL_TILE, per)
    if form == "grid":
        assert (tt, ct) == resolve_grid_tiles(t, c) == (DEFAULT_TRIAL_TILE,
                                                        DEFAULT_CLIENT_TILE)
    else:
        assert ct == 1
        assert policy_core.trial_grid_vmem_bytes(
            tt, m, window, -(-r // window),
            policy in policy_core.PLAN_POLICIES) \
            <= policy_core.SCOPED_VMEM_BYTES or tt == DEFAULT_TRIAL_TILE


def test_simconfig_rejects_unknown_tiles_mode():
    with pytest.raises(ValueError):
        _cfg(tiles="turbo")


# -------------------------------------------------------- tuner sweep

def test_candidate_tiles_clamped_and_deduped():
    cands = autotune.candidate_tiles(6, form="batch")
    assert cands == [(tt, 1) for tt in sorted({t for t, _ in cands})]
    assert all(1 <= tt <= 6 for tt, _ in cands)
    grid = autotune.candidate_tiles(100, 5, form="grid")
    assert all(tt * ct <= autotune.MAX_STREAM_SUBLANES
               for tt, ct in grid)
    assert all(ct <= 5 for _, ct in grid)


def test_tune_config_deterministic_table_bytes(monkeypatch, tmp_path):
    """Same config + same injected timer -> byte-identical tables."""
    cfg = _cfg()
    pol = PolicyConfig(name="ect", threshold=0.05, rng="lcg")

    def fake_timer():
        costs = iter(range(100))
        return lambda run: float(next(costs))     # first candidate wins

    blobs = []
    for name in ("a.json", "b.json"):
        p = tmp_path / name
        monkeypatch.setenv("SCHED_TUNE_PATH", str(p))
        key, entry = autotune.tune_config(cfg, pol, timer=fake_timer())
        assert table.load_table()[key]["trial_tile"] == entry["trial_tile"]
        blobs.append(p.read_bytes())
    assert blobs[0] == blobs[1]


# ------------------------------------------- association property (§16)

@pytest.mark.parametrize("policy", KERNEL_POLICIES)
def test_tuned_tiles_keep_backends_bit_exact(monkeypatch, tmp_path,
                                             policy):
    """A tuned (non-default) trial tile must not move ANY result: the
    trial-grid kernel, the jax engine twin (which finds the tuned entry
    through the kernel-key fallback) and the lax.map sequential oracle
    stay bitwise identical under tiles="tuned"."""
    monkeypatch.setenv("SCHED_TUNE_PATH", str(tmp_path / "TUNE.json"))
    cfg = _cfg(n_trials=5, tiles="tuned")
    pol = PolicyConfig(name=policy, threshold=0.5, rng="lcg")
    log_cfg = simulate.default_log_cfg(cfg)
    # a deliberately odd depth (not the default 8, not T): tests the
    # inert-padding path of the tuned lowering too
    table.store(table.config_key(
        policy=policy, backend="kernel", n_servers=cfg.n_servers,
        n_requests=cfg.n_requests, n_clients=1, n_trials=cfg.n_trials,
        window_size=cfg.window_size), {"trial_tile": 3, "client_tile": 1})
    key = jax.random.key(0)
    kern = simulate.run_trials(key, cfg, pol, log_cfg)
    eng = simulate.run_trials(
        key, dataclasses.replace(cfg, backend="jax"), pol, log_cfg)
    keys = jax.random.split(key, cfg.n_trials)
    seq = jax.jit(lambda ks: jax.lax.map(
        lambda k: simulate.run_one_trial(k, cfg, pol, log_cfg), ks))(keys)
    for other, tag in ((eng, "jax engine"), (seq, "sequential oracle")):
        for f in kern._fields:
            assert (np.asarray(getattr(kern, f))
                    == np.asarray(getattr(other, f))).all(), \
                (policy, tag, f)
