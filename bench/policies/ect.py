"""ECT (expected completion time), the repo's rate-aware extension.

Steps run in ascending object id.  The target is the server with the
least ``(load + length) / estimated rate`` (first one on a tie); the
request leaves its default server ``object_id mod M`` only when that
saves more than ``threshold`` seconds."""

import jax.numpy as jnp


def rank_key(obj, step_len, opens):
    """Processing order: opening requests by object id, then the rest."""
    big = jnp.iinfo(jnp.int32).max
    return jnp.argsort(jnp.where(opens, obj, big), stable=True)


def plan(log, m):
    return None


def choose(log, plan, pos, default, length, threshold, m):
    score = (log["loads"] + length) / log["est"]
    target = jnp.argmin(score).astype(jnp.int32)
    benefit = score[default] - score[target]
    return jnp.where(benefit > threshold, target, default).astype(jnp.int32)
