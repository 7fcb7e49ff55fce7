"""Profile-guided lowering autotuner (DESIGN.md §16).

Three pieces:

* :mod:`repro.tune.table` — the versioned on-disk tile table
  (``TUNE_sched.json``) and :func:`~repro.tune.table.resolve_sim_tiles`,
  the ONE resolution point `simulate._sched_trials` routes every
  backend's (trial_tile, client_tile) through;
* :mod:`repro.tune.profile` — the stage scopes (``jax.named_scope``,
  read back from the device trace) around the sweep's pipeline stages,
  plus the differential kernel phase profiler built on the kernel's
  ``ablate`` levels;
* :mod:`repro.tune.autotune` — the candidate sweep that times tile
  shapes and caches the winner (imported lazily: it depends on
  `repro.core.simulate`, which itself imports :mod:`repro.tune.table`).

``python -m repro.tune --print`` dumps the cached table;
``python -m repro.tune --tune <preset>`` re-tunes a named config.
"""

from repro.tune import profile, table  # noqa: F401
from repro.tune.table import (config_key, load_table,  # noqa: F401
                              resolve_sim_tiles, save_table)
