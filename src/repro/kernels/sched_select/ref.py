"""Pure-jnp oracles for the sched_select kernels (bit-identical math).

Replays the same LCG, selection, threshold guard and Eq. (1)-(3) updates
with a ``lax.scan`` carry — the exact state-passing formulation the kernel
replaces with VMEM-resident streaming.  ``sched_stream_ref`` mirrors the
temporal kernel (windows, drain, completion feedback, TRH rank plan) on
the packed (4, M) log tensor of `repro.core.policy_core`.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.core.policy_core import (ROW_EST, ROW_EWMA, ROW_LOADS, ROW_PROBS,
                                    client_stream_metrics, drain_loads,
                                    masked_client_mean, permute_from_sorted,
                                    permute_to_sorted, rank_desc,
                                    recursive_average_bounds,
                                    renormalize_probs, resolve_client_tile,
                                    stream_metrics, window_decrements)


def _lcg(rng: jax.Array) -> jax.Array:
    return rng * jnp.uint32(1664525) + jnp.uint32(1013904223)


def _rand_server(rng: jax.Array, m: int) -> jax.Array:
    return jax.lax.rem((rng >> jnp.uint32(8)).astype(jnp.int32)
                       & jnp.int32(0x7FFFFFFF), m)


def sched_select_ref(object_ids: jax.Array, lengths: jax.Array,
                     init_loads: jax.Array, seed: jax.Array, *,
                     n_servers: int, threshold: float, lam: float,
                     policy: str) -> Tuple[jax.Array, jax.Array]:
    """Single client. object_ids/lengths: (N,); init_loads: (M_pad,)."""
    m_pad = init_loads.shape[0]
    m = n_servers
    lane = jnp.arange(m_pad)
    valid = lane < m
    loads0 = jnp.where(valid, init_loads, 3.4e38).astype(jnp.float32)
    probs0 = jnp.where(valid, 1.0 / m, 0.0).astype(jnp.float32)

    def step(carry, xs):
        loads, probs, rng = carry
        obj, ln = xs
        default = jax.lax.rem(obj, m)
        if policy == "minload":
            target = jnp.argmin(loads).astype(jnp.int32)
        elif policy == "two_random":
            r1 = _lcg(rng)
            r2 = _lcg(r1)
            rng = r2
            c1, c2 = _rand_server(r1, m), _rand_server(r2, m)
            target = jnp.where(loads[c1] <= loads[c2], c1, c2).astype(jnp.int32)
        else:
            raise ValueError(policy)
        choose = jnp.where(loads[default] - loads[target] > threshold,
                           target, default).astype(jnp.int32)
        onehot = lane == choose
        loads = jnp.where(onehot, loads + ln, loads)
        p_i = probs[choose]
        l_i = loads[choose]
        e = jnp.exp(-l_i / lam)
        decayed = p_i * e                                    # Eq. (2)
        delta = p_i * (1.0 - e) / (m - 1)                    # Eq. (3)
        probs = jnp.where(onehot, decayed,
                          jnp.where(valid, probs + delta, 0.0))
        return (loads, probs, rng), choose

    (loads, probs, _), choices = jax.lax.scan(
        step, (loads0, probs0, seed.astype(jnp.uint32)),
        (object_ids, lengths))
    return choices, jnp.where(valid, loads, 0.0)


def sched_stream_ref(object_ids: jax.Array, lengths: jax.Array,
                     valid: jax.Array, table: jax.Array, seed: jax.Array,
                     win_rates: jax.Array, *, n_servers: int,
                     window_size: int, threshold: float, lam: float,
                     alpha: float = 0.25, window_dt: float = 0.0,
                     policy: str = "ect", observe: bool = True,
                     renorm: bool = True, nltr_n: int = 2,
                     probe_choices: int = 2
                     ) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Single-client oracle for the temporal stream kernel.

    Same signature semantics as ``ops.sched_stream`` (single-client form):
    object_ids/lengths/valid (N,), table (4, M) packed log tensor, seed ()
    uint32, win_rates (W, M).  Scan-carried replay of the identical
    per-request decision math, per-window renormalization and drain; the
    sort-based policies (mlml/nltr) replay the kernel's in-VMEM window
    plan — the shared all-pairs rank / permutation-apply primitives and
    recursive-average section bounds (DESIGN.md §10, §13, §19) — processing
    in length-desc order and unsorting decisions with the same inverse
    apply.
    """
    m = n_servers
    n_win = win_rates.shape[0]
    obj_w = object_ids.reshape(n_win, window_size)
    len_w = lengths.reshape(n_win, window_size)
    val_w = valid.reshape(n_win, window_size)
    sort_policy = policy in ("mlml", "nltr")
    k_sections = 2 ** nltr_n
    sec_size = max(m // k_sections, 1)

    loads0 = table[ROW_LOADS].astype(jnp.float32)
    probs0 = table[ROW_PROBS].astype(jnp.float32)
    ewma0 = table[ROW_EWMA].astype(jnp.float32)
    est0 = table[ROW_EST].astype(jnp.float32)
    lane = jnp.arange(m)

    def window(carry, xs):
        loads, probs, ewma, est, rng = carry
        obj, lens, val, rates, dec = xs
        # window-start plan (DESIGN.md §13, shared with the kernel):
        # all-pairs rank == inverse of the stable argsort(-probs)
        # permutation; one permutation apply lands the server ids in
        # rank order — no sort network, no backend argsort
        rank_srv, _ = rank_desc(probs)
        order = permute_to_sorted(rank_srv,
                                  (lane.astype(jnp.int32),))[0]
        if sort_policy:
            # §13 fast path: rank the request block once, land
            # obj/len/valid in length-desc order with one permutation
            # apply — the same relocations a stable argsort + take
            # would perform
            rank_req, mkeys = rank_desc(lens, valid=val)
            obj_p, len_p, val_p = permute_to_sorted(
                rank_req, (obj, lens, val.astype(jnp.int32)))
            val_p = val_p != 0
            if policy == "nltr":
                nvalid = jnp.sum(val.astype(jnp.int32)).reshape(1)
                skeys = permute_to_sorted(rank_req, (mkeys,))[0]
                bounds = recursive_average_bounds(skeys, nvalid, nltr_n)
        else:
            obj_p, len_p, val_p = obj, lens, val

        def step(c, x):
            loads, probs, ewma, est, rng = c
            pos, o, ln, v = x
            default = jax.lax.rem(o, m)
            if policy == "rr":
                target = default
            elif policy == "minload":
                target = jnp.argmin(loads).astype(jnp.int32)
            elif policy == "ect":
                target = jnp.argmin((loads + ln) / est).astype(jnp.int32)
            elif policy == "mlml":
                target = order[jax.lax.rem(pos, m)].astype(jnp.int32)
            elif policy == "nltr":
                sec = jnp.clip(jnp.sum((pos >= bounds).astype(jnp.int32)),
                               0, k_sections - 1)
                lo = sec * sec_size
                r1 = _lcg(rng)
                r2 = _lcg(r1)
                rng = r2
                c1 = order[lo + _rand_server(r1, sec_size)].astype(jnp.int32)
                c2 = order[lo + _rand_server(r2, sec_size)].astype(jnp.int32)
                target = jnp.where(loads[c1] <= loads[c2], c1,
                                   c2).astype(jnp.int32)
            elif policy == "two_choice":
                target = default
                best_l = loads[default]
                for _ in range(probe_choices - 1):
                    rng = _lcg(rng)
                    c2 = _rand_server(rng, m)
                    better = loads[c2] < best_l
                    target = jnp.where(better, c2, target).astype(jnp.int32)
                    best_l = jnp.where(better, loads[c2], best_l)
            elif policy in ("two_random", "trh"):
                r1 = _lcg(rng)
                r2 = _lcg(r1)
                rng = r2
                if policy == "two_random":
                    c1, c2 = _rand_server(r1, m), _rand_server(r2, m)
                else:
                    half = max(m // 2, 1)
                    c1 = order[_rand_server(r1, half)].astype(jnp.int32)
                    c2 = order[_rand_server(r2, half)].astype(jnp.int32)
                target = jnp.where(loads[c1] <= loads[c2], c1,
                                   c2).astype(jnp.int32)
            else:
                raise ValueError(policy)
            if policy == "rr":
                choose = default
            elif policy == "ect":
                benefit = ((loads[default] + ln) / est[default]
                           - (loads[target] + ln) / est[target])
                choose = jnp.where(benefit > threshold, target,
                                   default).astype(jnp.int32)
            else:
                benefit = loads[default] - loads[target]
                choose = jnp.where(benefit > threshold, target,
                                   default).astype(jnp.int32)
            onehot = lane == choose
            upd = onehot & v
            new_loads = jnp.where(upd, loads + ln, loads)
            # one-hot masked sums, exactly as the kernel extracts lanes
            p_i = jnp.sum(jnp.where(onehot, probs, 0.0))
            l_i = jnp.sum(jnp.where(onehot, new_loads, 0.0))
            e = jnp.exp(-l_i / lam)
            decayed = p_i * e                                # Eq. (2)
            delta = p_i * (1.0 - e) / (m - 1)                # Eq. (3)
            new_probs = jnp.where(onehot, decayed, probs + delta)
            probs = jnp.where(v, new_probs, probs)
            loads = new_loads
            lat = loads[choose] / jnp.maximum(rates[choose], 1e-6)
            if observe:
                mbps = ln / jnp.maximum(lat, 1e-9)
                old = ewma[choose]
                new = jnp.where(old == 0.0, mbps,
                                # contract-ok: CC-FMA EWMA row is 1e-6-soft (§9)
                                (1 - alpha) * old + alpha * mbps)
                ewma = jnp.where(upd, jnp.where(onehot, new, ewma), ewma)
                dflt = jnp.maximum(jnp.max(ewma), 1.0)
                est = jnp.where(v, jnp.where(ewma > 0, ewma, dflt), est)
            return (loads, probs, ewma, est, rng), \
                (choose, jnp.where(v, lat, 0.0))

        pos = jnp.arange(window_size, dtype=jnp.int32)
        (loads, probs, ewma, est, rng), (ch, lt) = jax.lax.scan(
            step, (loads, probs, ewma, est, rng), (pos, obj_p, len_p, val_p))
        if sort_policy:
            # unsort with ONE vectorized inverse apply (§13) — bit-equal
            # to the one-hot scatter it replaces: every value only MOVES
            ch, lt = permute_from_sorted(rank_req, (ch, lt))
        if renorm:
            # shared core: lane_sum's explicit halving tree (§9 contract)
            probs = renormalize_probs(probs)
        if window_dt:
            # shared core: dec materialized outside the scan (§9 contract)
            loads = drain_loads(loads, rates, window_dt, dec=dec)
        return (loads, probs, ewma, est, rng), (ch, lt, loads)

    rates_f = win_rates.astype(jnp.float32)
    carry0 = (loads0, probs0, ewma0, est0, seed.astype(jnp.uint32))
    (loads, probs, ewma, est, _), (choices, lats, wloads) = jax.lax.scan(
        window, carry0, (obj_w, len_w, val_w, rates_f,
                         window_decrements(rates_f, window_dt)))
    final = jnp.stack([loads, probs, ewma, est])
    return choices.reshape(-1), lats.reshape(-1), final, wloads


def sched_stream_batch_ref(object_ids: jax.Array, lengths: jax.Array,
                           valid: jax.Array, tables: jax.Array,
                           seeds: jax.Array, win_rates: jax.Array, *,
                           n_servers: int, window_size: int,
                           threshold: float, lam: float, alpha: float = 0.25,
                           window_dt: float = 0.0, policy: str = "ect",
                           observe: bool = True, renorm: bool = True,
                           nltr_n: int = 2, probe_choices: int = 2
                           ) -> Tuple[jax.Array, jax.Array, jax.Array,
                                      jax.Array, jax.Array]:
    """Trial-batched oracle for ``ops.sched_stream_batch``: the per-trial
    scan replay vmapped over the leading trial axis, plus the fused
    metrics twin (`policy_core.stream_metrics`) over the per-trial
    latencies.  Same shapes as the grid kernel: object_ids/lengths/valid
    (T, N), tables (T, 4, M), seeds (T,), win_rates (T, W, M); returns
    (choices, latencies, final_tables, window_loads, metrics
    (T, N_METRICS))."""
    one = functools.partial(
        sched_stream_ref, n_servers=n_servers, window_size=window_size,
        threshold=threshold, lam=lam, alpha=alpha, window_dt=window_dt,
        policy=policy, observe=observe, renorm=renorm, nltr_n=nltr_n,
        probe_choices=probe_choices)
    choices, lats, finals, wloads = jax.vmap(one)(
        object_ids, lengths, valid, tables, seeds, win_rates)
    metrics = stream_metrics(lats, valid.astype(bool), window_dt,
                             window_size)
    return choices, lats, finals, wloads, metrics


def sched_stream_grid_ref(object_ids: jax.Array, lengths: jax.Array,
                          valid: jax.Array, tables: jax.Array,
                          seeds: jax.Array, win_rates: jax.Array, *,
                          n_servers: int, window_size: int,
                          threshold: float, lam: float, alpha: float = 0.25,
                          window_dt: float = 0.0, policy: str = "ect",
                          observe: bool = True, renorm: bool = True,
                          client_tile=None, nltr_n: int = 2,
                          probe_choices: int = 2):
    """2-D (trials × clients) oracle for ``ops.sched_stream_grid``: the
    per-stream scan replay vmapped over BOTH leading axes (a trial's
    clients share its ``win_rates`` trace), plus the cross-client merge
    twins — `policy_core.masked_client_mean` over the per-client window
    loads and `policy_core.client_stream_metrics` over the per-client
    fused metric rows (its MET_P99 lane the nearest-rank p99 over the
    trial's MERGED latency block, DESIGN.md §14), with a client REAL iff
    its slice holds any valid request.  Same shapes as the grid kernel:
    object_ids/lengths/valid (T, C, N), tables (T, C, 4, M), seeds
    (T, C), win_rates (T, W, M); returns (choices, latencies,
    final_tables, window_loads, metrics (T, C, N_METRICS), cm_wloads
    (T, W, M), cm_metrics (T, N_CMETRICS), cm_lats (T, C, N) masked
    latencies, cm_lval (T, C, N) 0/1 validity).
    """
    one = functools.partial(
        sched_stream_ref, n_servers=n_servers, window_size=window_size,
        threshold=threshold, lam=lam, alpha=alpha, window_dt=window_dt,
        policy=policy, observe=observe, renorm=renorm, nltr_n=nltr_n,
        probe_choices=probe_choices)
    per_trial = jax.vmap(one, in_axes=(0, 0, 0, 0, 0, None))
    choices, lats, finals, wloads = jax.vmap(per_trial)(
        object_ids, lengths, valid, tables, seeds, win_rates)
    validb = valid.astype(bool)
    metrics = stream_metrics(lats, validb, window_dt, window_size)
    ct = resolve_client_tile(object_ids.shape[1], client_tile)
    cvalid = jnp.any(validb, axis=-1)                        # (T, C)
    cm_lats = jnp.where(validb, lats, 0.0)                   # (T, C, N)
    cm_lval = jnp.where(validb, 1.0, 0.0)
    cm_wl = jax.vmap(lambda w, v: masked_client_mean(w, v, ct))(
        wloads, cvalid)
    cm_met = jax.vmap(
        lambda m, v, ml, mv: client_stream_metrics(
            m, v, ct, merged_lats=ml, merged_valid=mv)
    )(metrics, cvalid, cm_lats, validb)
    return (choices, lats, finals, wloads, metrics, cm_wl, cm_met,
            cm_lats, cm_lval)
