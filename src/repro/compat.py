"""The few JAX calls the repo makes in several places, in one form each.

* ``simple_keystr``  — ``jax.tree_util.keystr(kp, simple=True,
  separator="/")``.  Checkpoint manifests, sharding rules and the
  optimizer's decay mask key off these stable path strings.
* ``make_mesh``      — ``jax.make_mesh`` with ``axis_types=Auto``.
* ``shard_map``      — ``jax.shard_map``.
* ``shard_map_unchecked`` — ``shard_map`` with the static replication
  check disabled (``check_vma=False``).
"""

from __future__ import annotations

import jax
import jax.tree_util as jtu

shard_map = jax.shard_map


def simple_keystr(kp, separator: str = "/") -> str:
    """`keystr(kp, simple=True, separator=...)`."""
    return jtu.keystr(kp, simple=True, separator=separator)


def shard_map_unchecked(f, mesh, in_specs, out_specs):
    """``shard_map`` with the static replication check disabled.

    The sharded sweep (parallel/sweep.py) replicates its merged outputs
    across the client mesh axis via ``all_gather`` + the pinned
    ``policy_core.tree_sum`` fold; the checker cannot infer replication
    through tree_sum's pad/slice ops and rejects the ``out_specs``, so
    the check is turned off (the replication is real: every device
    gathers identical operands and folds them with the same
    deterministic tree).
    """
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def make_mesh(shape, axis_names):
    """`jax.make_mesh` with explicit Auto axis types."""
    return jax.make_mesh(
        shape, axis_names,
        axis_types=(jax.sharding.AxisType.Auto,) * len(axis_names))
