"""Versioned on-disk lowering-tile table + the ONE tuned-tile resolver.

The autotuner (`repro.tune.autotune`) times candidate (trial_tile,
client_tile) shapes per configuration and caches the winner here, in a
flat JSON table at the repo root (``TUNE_sched.json``, committed next to
``BENCH_sched.json`` so tuned runs are reproducible from a checkout; the
``SCHED_TUNE_PATH`` env var points tests and experiments at a private
table).

Keying (DESIGN.md §16): one entry per ``(policy, backend, M, R, C, T,
window_size, device_count, form)`` — everything the winning lowering
shape can depend on.  Lookup falls back from the exact backend to the
CANONICAL ``backend="kernel"`` entry: the client tile is an ASSOCIATION
parameter (it fixes the cross-client merge grouping, DESIGN.md §11), so
a jax-backend run of a kernel-tuned shape must resolve the *same* tiles
or the two backends would agree on different bit-exact results.

Robustness: a missing, unreadable, corrupt, or stale-``version`` table
degrades to the static defaults — tuning is an optimization, never a
correctness dependency, so nothing in this module raises on bad cache
state.
"""

from __future__ import annotations

import json
import os
from typing import Dict, Optional, Tuple

from repro.core.policy_core import (resolve_client_tile, resolve_grid_tiles,
                                    resolve_trial_tile)

TABLE_VERSION = 1
TABLE_BASENAME = "TUNE_sched.json"
ENV_PATH = "SCHED_TUNE_PATH"

# simulate dispatch forms: "batch" = the 1-D trial grid (shared_log),
# "grid" = the 2-D trials x clients grid (per_client)
FORMS = ("batch", "grid")

TILE_MODES = ("default", "tuned", "fused")


def default_path() -> str:
    env = os.environ.get(ENV_PATH)
    if env:
        return env
    here = os.path.dirname(os.path.abspath(__file__))
    # src/repro/tune -> repo root
    return os.path.join(os.path.dirname(os.path.dirname(
        os.path.dirname(here))), TABLE_BASENAME)


def config_key(*, policy: str, backend: str, n_servers: int,
               n_requests: int, n_clients: int, n_trials: int,
               window_size: int, device_count: int = 1,
               form: str = "batch") -> str:
    """Canonical string key of one tuning configuration."""
    if form not in FORMS:
        raise ValueError(f"form={form!r} must be one of {FORMS}")
    return (f"policy={policy}|backend={backend}|M={n_servers}"
            f"|R={n_requests}|C={n_clients}|T={n_trials}"
            f"|W={window_size}|D={device_count}|form={form}")


def load_table(path: Optional[str] = None) -> Dict[str, dict]:
    """The cached ``{key: entry}`` map; {} on ANY bad cache state
    (missing file, unreadable bytes, non-JSON, wrong schema, stale
    version) — never raises."""
    path = path or default_path()
    try:
        with open(path, "r", encoding="utf-8") as fh:
            raw = json.load(fh)
    except (OSError, ValueError):
        return {}
    if not isinstance(raw, dict) or raw.get("version") != TABLE_VERSION:
        return {}
    entries = raw.get("entries")
    if not isinstance(entries, dict):
        return {}
    out: Dict[str, dict] = {}
    for key, entry in sorted(entries.items()):
        if isinstance(key, str) and isinstance(entry, dict):
            out[key] = dict(entry)
    return out


def save_table(entries: Dict[str, dict], path: Optional[str] = None) -> str:
    """Write the versioned table (sorted keys — byte-deterministic for a
    given entry map).  Returns the path written."""
    path = path or default_path()
    payload = {"version": TABLE_VERSION,
               "entries": {k: entries[k] for k in sorted(entries)}}
    with open(path, "w", encoding="utf-8") as fh:
        json.dump(payload, fh, indent=1, sort_keys=True)
        fh.write("\n")
    return path


def store(key: str, entry: dict, path: Optional[str] = None) -> str:
    entries = load_table(path)
    entries[key] = dict(entry)
    return save_table(entries, path)


def _entry_tiles(entry: Optional[dict]) -> Tuple[Optional[int],
                                                 Optional[int]]:
    if not isinstance(entry, dict):
        return None, None
    tt, ct = entry.get("trial_tile"), entry.get("client_tile")
    tt = int(tt) if isinstance(tt, (int, float)) and tt >= 1 else None
    ct = int(ct) if isinstance(ct, (int, float)) and ct >= 1 else None
    return tt, ct


def lookup(*, policy: str, backend: str, n_servers: int, n_requests: int,
           n_clients: int, n_trials: int, window_size: int,
           device_count: int = 1, form: str = "batch",
           path: Optional[str] = None) -> Optional[dict]:
    """The cached entry for a configuration, trying the exact backend
    first and falling back to the canonical ``kernel`` entry (see module
    docstring — association safety across backends)."""
    entries = load_table(path)
    for be in (backend, "kernel"):
        entry = entries.get(config_key(
            policy=policy, backend=be, n_servers=n_servers,
            n_requests=n_requests, n_clients=n_clients, n_trials=n_trials,
            window_size=window_size, device_count=device_count, form=form))
        if entry is not None:
            return entry
    return None


def resolve_sim_tiles(*, mode: str, policy: str, backend: str,
                      n_servers: int, n_requests: int, n_clients: int,
                      n_trials: int, window_size: int, device_count: int = 1,
                      form: str = "batch", trial_tile=None, client_tile=None,
                      path: Optional[str] = None) -> Tuple[int, int]:
    """THE tuned-tile resolution point (DESIGN.md §16).

    `simulate._sched_trials` calls this ONCE per dispatch and threads
    the returned pair through every layer — kernel grid, engine twin,
    the jax cross-client fold and the sharded sweep — so the tiles stay
    association parameters no matter which mode picked them: tuning
    changes *which* bit-exact result every layer agrees on, never the
    agreement itself.  Explicit ``trial_tile``/``client_tile`` settings
    always win over the table; ``mode``:

    * ``"default"`` — the `resolve_trial_tile` / `resolve_client_tile`
      defaults (the fallback for every bad-cache state); for
      ``form="batch"`` the trial tile follows the row depth, per device
      (DESIGN.md §20), as the kernel's own default does;
    * ``"fused"``   — the `resolve_grid_tiles` fused multi-trial client
      block (deepen the trial tile when the client tile is small);
    * ``"tuned"``   — the cached autotuner winner for this
      configuration, clamped through the static resolvers; a cache miss
      degrades to ``"fused"`` (the profile-guided static heuristic).
    """
    if mode not in TILE_MODES:
        raise ValueError(f"tiles mode {mode!r} must be one of {TILE_MODES}")
    if mode == "tuned":
        entry = lookup(policy=policy, backend=backend, n_servers=n_servers,
                       n_requests=n_requests, n_clients=n_clients,
                       n_trials=n_trials, window_size=window_size,
                       device_count=device_count, form=form, path=path)
        tuned_tt, tuned_ct = _entry_tiles(entry)
        if tuned_tt is None and tuned_ct is None:
            mode = "fused"          # cache miss: the static heuristic
        else:
            tt = resolve_trial_tile(
                n_trials, tuned_tt if trial_tile is None else trial_tile)
            ct = resolve_client_tile(
                n_clients, tuned_ct if client_tile is None else client_tile)
            return tt, ct
    if mode == "fused":
        return resolve_grid_tiles(n_trials, n_clients, trial_tile,
                                  client_tile)
    ct = resolve_client_tile(n_clients, client_tile)
    if form != "batch":
        return resolve_trial_tile(n_trials, trial_tile), ct
    return resolve_trial_tile(
        n_trials, trial_tile, n_servers=n_servers, n_requests=n_requests,
        window_size=window_size, policy=policy, n_devices=device_count), ct
