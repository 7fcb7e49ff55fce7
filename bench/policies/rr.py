"""Round-robin, the paper's baseline.

Steps run in ascending object id, as under ECT.  Every request stays on
its default server ``object_id mod M``: no target, no guard."""

import jax.numpy as jnp


def rank_key(obj, step_len, opens):
    """Processing order: opening requests by object id, then the rest."""
    big = jnp.iinfo(jnp.int32).max
    return jnp.argsort(jnp.where(opens, obj, big), stable=True)


def plan(log, m):
    return None


def choose(log, plan, pos, default, length, threshold, m):
    return default
