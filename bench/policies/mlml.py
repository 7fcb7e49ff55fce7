"""MLML (Max-Length Min-Load), the paper's Algorithm 1.

At window start the servers are ranked by selection probability, highest
first, and the window's steps by summed length, longest first (ties by
object id).  The k-th step's target is the (k mod M)-th server; the
request leaves its default server ``object_id mod M`` only when the
target's load is lower by more than ``threshold`` MB."""

import jax.numpy as jnp


def rank_key(obj, step_len, opens):
    """Processing order: opening requests longest first, then the rest."""
    return jnp.lexsort((obj, -step_len.astype(jnp.float32), ~opens))


def plan(log, m):
    return jnp.argsort(-log["probs"], stable=True).astype(jnp.int32)


def choose(log, plan, pos, default, length, threshold, m):
    target = plan[pos % m]
    benefit = log["loads"][default] - log["loads"][target]
    return jnp.where(benefit > threshold, target, default).astype(jnp.int32)
