"""Sharded Monte-Carlo sweep dispatch (DESIGN.md §12).

:func:`run_sweep` wraps :func:`engine.run_stream_batch` — BOTH backends
— in ``shard_map`` over a sweep mesh (``("trials",)`` or ``("trials",
"clients")``, `launch.mesh.make_sweep_mesh`), sharding the (T[, C], …)
request/latency/log stacks across devices while the per-trial rate
traces stay replicated on the client axis (a trial's clients share its
cluster trace, on one device or eight).

Bit-exactness is the whole design:

* the TRIAL axis is embarrassingly parallel — per-stream outputs are
  device-count-invariant provided every lowering-sensitive association
  parameter resolves identically on every device, so the effective
  trial tile is pinned from the GLOBAL trial count (the single-device
  resolution) and each device's shard is padded up to at least one full
  tile;
* the CLIENT axis adds one more association level to the cross-client
  merge: each device folds its local clients with
  `policy_core.masked_client_sum` (on the kernel backend with
  ``merge_mean=False`` — raw SUM blocks, a mean is not cross-device
  composable), then `policy_core.psum_tree` — ``all_gather`` + the
  pinned `tree_sum` halving tree, never a backend ``psum`` — folds the
  per-device partials in mesh-coordinate order.  The device count is
  resolved by shared code (`policy_core.resolve_shard_width`) exactly
  like ``client_tile``, and `policy_core.sharded_client_sum` is the
  host oracle of the whole two-level association.

Padding: the trial axis pads by REPLICATING trial 0 (padded trials
recompute a real trial and are dropped after the dispatch — merges are
per-trial, so they never contaminate anything); the client axis pads
with PHANTOM clients (``valid=False`` slices) that every masked merge
excludes, exactly like the 2-D grid kernel's own client padding.
"""

from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.compat import shard_map_unchecked
from repro.core import engine, policy_core
from repro.launch.mesh import make_sweep_mesh
from repro.tune import profile as tune_profile


class SweepMerge(NamedTuple):
    """Per-trial cross-CLIENT aggregates of the sharded (T, C) sweep,
    merged across the client mesh axis with the DESIGN.md §12
    association (per-device `masked_client_sum` partials folded by
    `psum_tree`; maxes by ``pmax``, integer probe counts by ``psum``).
    The sharded twin of :class:`engine.ClientMerge`, uniform across
    backends — exactly the rows `simulate._run_batched`'s per_client
    fold consumes.

    ``p99`` is the GLOBAL cross-client nearest-rank p99 (DESIGN.md
    §14): each device's local merged latency block (the kernel path's
    ``ClientMerge.lats``/``lats_valid`` pair, or the jax twin
    `engine.grouped_latency_block`) is ``all_gather``ed over the client
    mesh axis and bisected ONCE with `policy_core.nearest_rank_p99` —
    which is order- and layout-insensitive, so the gathered shard order
    cannot drift the result vs the single-device merged block."""

    window_loads_mean: jax.Array  # (T, W, M) masked client-mean snapshots
    phase_time: jax.Array         # (T,) merged makespan over real clients
    probe_msgs: jax.Array         # (T,) int32 probe total over real clients
    p99: jax.Array                # (T,) global merged nearest-rank p99


def _edge_pad(tree, axis: int, new: int):
    """Pad ``axis`` up to length ``new`` by replicating index 0 (cheap,
    deterministic, finite — the padded slots recompute slot 0).

    Implemented as a GATHER (clipped-index take), not
    broadcast+concatenate: under jit, GSPMD mispartitions a concatenate
    feeding a shard_map operand that is replicated on one axis of a
    2-D mesh — devices receive wrong (even nonexistent) trace rows.
    The gather form partitions correctly; the parity tests pin it."""
    def one(a):
        if a.shape[axis] == new:
            return a
        ar = jnp.arange(new)
        idx = jnp.where(ar < a.shape[axis], ar, 0)
        return a[(slice(None),) * axis + (idx,)]

    return None if tree is None else jax.tree.map(one, tree)


def run_sweep(states, works, keys, *, mesh_shape: Optional[Tuple[int, ...]],
              policy, log_cfg, window_size: int, backend: str = "kernel",
              group_steps: bool = True, traces=None, window_dt: float = 0.0,
              observe: Optional[bool] = None,
              trial_tile: Optional[int] = None,
              client_tile: Optional[int] = None):
    """The whole (T[, C]) sweep as one ``shard_map`` dispatch.

    Arguments mirror :func:`engine.run_stream_batch` (``states`` /
    ``works`` / ``keys`` with a ``(T,)`` or ``(T, C)`` leading batch,
    ``traces`` per-trial); ``mesh_shape`` picks the sweep mesh
    (`launch.mesh.make_sweep_mesh`).  Returns ``(result, metrics,
    sweep_merge)``: ``result``/``metrics`` exactly as the single-device
    dispatch returns them (padded trials/clients stripped), and
    ``sweep_merge`` a :class:`SweepMerge` for the (T, C) form (``None``
    for (T,), where there is nothing to merge).
    """
    from repro.kernels.sched_select import ops as kops

    mesh = make_sweep_mesh(mesh_shape)
    axes = mesh.axis_names
    t_dev = mesh.shape["trials"]
    c_dev = mesh.shape["clients"] if "clients" in axes else 1

    batch_shape = works.object_ids.shape[:-1]
    two_d = len(batch_shape) == 2
    if c_dev > 1 and not two_d:
        raise ValueError(
            f"mesh shape {tuple(mesh.shape.values())} shards a client axis "
            "but the batch has no client axis (pass (T, C) stacks or a "
            "(trials,) mesh)")
    t = batch_shape[0]
    if observe is None:
        observe = traces is not None

    # ---- trial-axis padding: replicate trial 0 up to t_dev equal shards
    # of at least one full trial tile.  The tile is a LOWERING parameter
    # (XLA specializes elementwise code to the block shape), so it must
    # resolve on every device exactly as the single-device dispatch
    # resolves it from the global T: pin the globally-resolved tile and
    # keep every shard at least that long so `ops`' resolve_trial_tile
    # of T_local cannot clamp it differently (DESIGN.md §12).
    tt_eff = kops.resolve_trial_tile(t, trial_tile)
    t_loc = max(-(-t // t_dev), tt_eff) if backend == "kernel" \
        else -(-t // t_dev)
    t_pad = t_loc * t_dev

    # ---- client-axis padding: phantoms up to c_dev equal shards (the
    # shard width is the association parameter the host oracle
    # `policy_core.sharded_client_sum` re-derives)
    if two_d:
        c = batch_shape[1]
        shard_w = policy_core.resolve_shard_width(c, c_dev)
        c_pad = shard_w * c_dev
        if c_pad != c:
            states = _edge_pad(states, 1, c_pad)
            keys = _edge_pad(keys, 1, c_pad)
            works = _edge_pad(works, 1, c_pad)
            cmask = jnp.arange(c_pad) < c
            works = works._replace(
                valid=works.valid & cmask[None, :, None])
    states = _edge_pad(states, 0, t_pad)
    works = _edge_pad(works, 0, t_pad)
    keys = _edge_pad(keys, 0, t_pad)
    traces = _edge_pad(traces, 0, t_pad)

    spec_tc = P("trials", "clients") if (two_d and "clients" in axes) \
        else P("trials")
    collective = two_d and "clients" in axes

    def body(states, works, keys, traces):
        res, metrics, merged = engine.run_stream_batch(
            states, works, keys, policy=policy, log_cfg=log_cfg,
            window_size=window_size, group_steps=group_steps,
            traces=traces, window_dt=window_dt, observe=observe,
            trial_tile=tt_eff if backend == "kernel" else trial_tile,
            client_tile=client_tile, merge_mean=False, backend=backend)
        if not two_d:
            return res, metrics, None

        # ---- cross-client merge (stage ``merge``): per-device partials
        # with the local masked_client_sum association, folded across the
        # client mesh axis by psum_tree (sums), pmax (makespan) and psum
        # (integer probe counts)
        with tune_profile.stage("merge"):
            cvalid = jnp.any(works.valid, axis=-1)        # (t_loc, c_loc)
            c_loc = cvalid.shape[1]
            ct = policy_core.resolve_client_tile(c_loc, client_tile)
            if merged is not None:
                # kernel backend: the grid's merge shipped raw SUM blocks
                # (merge_mean=False above) plus the raw merged latency block
                wl_sum = merged.window_loads_mean
                n_real = merged.metrics[:, policy_core.MET_N_CLIENTS]
                phase_loc = merged.metrics[:, policy_core.MET_MAKESPAN]
                lats_loc = merged.lats
                lval_loc = merged.lats_valid != 0.0
            else:
                # jax backend: the same policy_core merges
                wl_sum = jax.vmap(
                    lambda w, v: policy_core.masked_client_sum(w, v, ct)
                )(res.window_loads, cvalid)
                n_real = jax.vmap(
                    lambda v: policy_core.masked_client_sum(
                        jnp.ones(v.shape, jnp.float32), v, ct))(cvalid)
                per = works.valid.shape[-1]
                w_open = ((jnp.arange(per) // window_size).astype(jnp.float32)
                          * jnp.float32(window_dt))
                comp = jnp.where(works.valid,
                                 w_open[None, None, :] + res.latencies, 0.0)
                phase_loc = jnp.max(comp, axis=(1, 2))
                lats_loc, lval_loc = engine.grouped_latency_block(
                    works, res.latencies, window_size, group_steps)
            probes_loc = jnp.sum(jnp.where(cvalid, res.probe_msgs, 0),
                                 axis=-1).astype(jnp.int32)
            if collective:
                wl_sum = policy_core.psum_tree(wl_sum, "clients")
                n_real = policy_core.psum_tree(n_real, "clients")
                phase_loc = jax.lax.pmax(phase_loc, "clients")
                probes_loc = jax.lax.psum(probes_loc, "clients")
                # global p99: gather every device's raw block and bisect
                # ONCE — `nearest_rank_p99` is order-insensitive, so the
                # shard-major gather layout is immaterial (DESIGN.md §14)
                lats_loc = jax.lax.all_gather(lats_loc, "clients", axis=1)
                lval_loc = jax.lax.all_gather(lval_loc, "clients", axis=1)
            t_loc = lats_loc.shape[0]
            p99 = policy_core.nearest_rank_p99(
                lats_loc.reshape(t_loc, -1), lval_loc.reshape(t_loc, -1))[:, 0]
            wl_mean = wl_sum / jnp.maximum(n_real, 1.0)[:, None, None]
            return res, metrics, SweepMerge(window_loads_mean=wl_mean,
                                            phase_time=phase_loc,
                                            probe_msgs=probes_loc,
                                            p99=p99)

    f = shard_map_unchecked(
        body, mesh,
        in_specs=(spec_tc, spec_tc, spec_tc, P("trials")),
        out_specs=(spec_tc, spec_tc, P("trials")))
    res, metrics, smerge = f(states, works, keys, traces)

    # ---- strip the padding back off
    def unpad(tree, clients: bool):
        if tree is None:
            return None
        tree = jax.tree.map(lambda a: a[:t], tree)
        if clients and two_d and c_pad != c:
            tree = jax.tree.map(lambda a: a[:, :c], tree)
        return tree

    res = unpad(res, clients=True)
    metrics = unpad(metrics, clients=True)
    smerge = unpad(smerge, clients=False)
    return res, metrics, smerge
