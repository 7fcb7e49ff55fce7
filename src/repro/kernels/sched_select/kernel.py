"""Pallas kernel: stream I/O requests through a VMEM-resident statistic log.

This is the TPU-native adaptation of the paper's core insight (DESIGN.md):
*replace remote probing with local state*.  Scheduling a request stream
against an M-server statistic table is a sequential-dependence loop whose
working set — the packed ``(4, M)`` log tensor of `repro.core.policy_core`
(rows ``loads / probs / ewma_lat / est_rates``) — is reused every
iteration: the kernel pins the whole table in one VMEM scratch for the
entire stream and emits one assignment per request, instead of bouncing
the carry through XLA's while-loop machinery (HBM round trips per
decision).

ONE ``pallas_call`` serves both forms.  Streams are laid out in
BLOCKS: a program instance owns ``s_tile`` independent streams on the
sublane axis, and every per-stream operand reaches the kernel as
``(s_tile, ...)`` — the block's two leading grid axes are squeezed, so
the (8, 128) block rule holds for ANY tile (the stream axis is always
the block's full dimension).  Trials are INDEPENDENT streams, so the
per-request decision loop vectorizes over the stream sublanes: every op
below acts on ``(s_tile, M_pad)`` tiles — the native f32 ``(8, 128)``
TPU tile at the default ``t_tile = 8`` — and one stream degenerates to
the original single-stream kernel bit-for-bit.  There is no
cross-stream gossip, exactly as in the paper §3.3.

* TRIAL-GRID form (DESIGN.md §9, :func:`sched_stream_call`): the whole
  Monte-Carlo sweep — T independent windowed `run_stream` traces — with
  ``grid = (T / t_tile, 1)`` and ``s_tile = t_tile``.
* 2-D (TRIALS × CLIENTS) GRID form (DESIGN.md §11,
  :func:`sched_stream_grid_call`): the per_client contention model — T
  trials, each partitioned over C private-log clients — with ``grid =
  (T / t_tile, C / c_tile)`` and ``s_tile = t_tile * c_tile`` (stream
  ``s`` of a block is trial ``s // c_tile``, client ``s % c_tile``).
  The rate/drain traces stay per-TRIAL (a trial's clients share its
  cluster schedule) and are broadcast over the client sublanes in-VMEM
  by exact selects.  The cross-client merges are NOT in the kernel:
  `ops.sched_stream_grid` folds the per-stream outputs with the
  `policy_core` merge twins, the same code the jax backend runs.

REQUEST LAYOUT (TPU lowering): the request/choice/latency streams are
stored window by window, each window padded with inert ``valid = 0``
lanes to ``ws_pad`` = the window rounded up to whole 128-lane vregs
(:func:`window_lanes`).  Every window then starts at a lane offset that
is a provable multiple of 128 — Mosaic refuses dynamic lane offsets it
cannot prove aligned — and the kernel reads each window ONCE into
registers, selects the request of step ``j`` with a one-hot masked sum
(a single non-zero term: an exact relocation, no per-lane ref access),
accumulates the window's choices and latencies the same way and writes
them back once.  The loop still runs exactly ``window_size`` decisions:
padding lanes are never scheduled and never advance the LCG.

Per window the kernel snapshots the probability ranking (TRH's plan),
loops the window's requests (selection → threshold guard → Eq. (1)-(3)
one-hot updates → completion feedback into the ewma/est rows), then
renormalizes the probability row and drains each server's queue at the
window's TRUE service rates (``advance_time`` semantics; rates streamed
in as a ``(W, M)`` input).  Policies (selected statically):

* ``minload``    — argmin of current load (greedy; ECT with unit rates);
* ``two_random`` — power-of-two-choices over ALL servers (no probe
  messages; the in-VMEM LCG supplies the randomness);
* ``ect``        — argmin expected completion time ``(load+len)/est_rate``
  on the client-ESTIMATED rate row (stale view — observations only);
* ``trh``        — Two Random from Top Half: two LCG draws over the
  lightest M/2 servers of the probability ranking (paper Alg. 2);
* ``rr``         — round-robin baseline (``object_id mod M``, no guard);
* ``two_choice`` — the SC'14 probing baseline: default + LCG-random
  candidates, lightest by live load (probes counted host-side);
* ``mlml``       — Max Length - Min Load (paper Alg. 1): the window's
  requests sorted by length desc, paired circularly with the
  probability-sorted servers;
* ``nltr``       — n-Level Two Random (paper Alg. 3): servers cut into
  ``K = 2**n`` sections of the probability ranking, requests cut into K
  sections by recursive average of the sorted lengths; two LCG draws
  inside the matching section.

All policies except ``rr`` apply the paper's redirect-threshold guard
against the round-robin default ``object_id mod M`` and the Eq. (1)-(3)
updates with one-hot *vector* writes (no scatter — TPU lanes update
masked).  SORT-BASED POLICIES (DESIGN.md §10, §13): the per-window
server ranking AND the MLML/nLTR request ordering run IN-VMEM through
`policy_core.rank_desc` — ONE all-pairs (key desc, index asc)
comparison per ranking instead of a compare-exchange network — and
`policy_core.permute_to_sorted`, which lands obj/len/valid (and the
server ids) in sorted order as a single masked-sum permutation apply
(no gather op; ``jnp.argsort`` does not lower inside a fused Pallas
body, and its tie/tree behaviour is a backend choice).  Rows wider than
one vreg (``M_pad`` or ``ws_pad`` over 128) rank and apply in 128-lane
blocks walked by loops (DESIGN.md §19), so the live all-pairs tile is
``(s_tile, 128, 128)`` at any width; a one-vreg row is the whole tile
itself.  The comparator
is a strict total order, so the permutation equals the engine's stable
``argsort`` bit-for-bit (the window's padding lanes rank after every
real request, so positions ``[0, window_size)`` are unchanged); nLTR's
section bounds come from the shared `policy_core.recursive_average_bounds`
on the sorted keys with `lane_sum`-associated means.  MLML/nLTR loop the
window in sorted order via POSITION one-hots, accumulate
decisions/latencies in sorted order, and unsort both with ONE vectorized
`policy_core.permute_from_sorted` apply per window; the fused metrics
then reduce in ORIGINAL request order, matching
`policy_core.stream_metrics` (maxima and the valid count are order-free
exact and collapse to vectorized masked reductions — only the latency
sum keeps the host twin's sequential per-request float-add chain).

FUSED METRICS (DESIGN.md §9): before a program instance retires, it
reduces its streams' per-step latencies — still VMEM-resident — into a
``(s_tile, MET_PAD)`` metrics row (makespan, nearest-rank p99 via f32
value bisection, latency sum in request order, latency max, valid count;
`policy_core.MET_*` layout), so the sweep's headline numbers never
round-trip through HBM.  ``policy_core.stream_metrics`` is the bit-exact
host twin.

``ref.py`` is the bit-exact jnp oracle; `engine.run_stream(backend=...)`
/ `engine.run_stream_batch` parity is asserted in tests/test_kernels.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.core.policy_core import (LANES, LCG_A, LCG_C, MET_LAT_MAX,
                                    MET_LAT_SUM, MET_MAKESPAN, MET_N_VALID,
                                    MET_P99, MET_PAD, N_ROWS, P99_BISECT_ITERS,
                                    P99_Q, PLAN_POLICIES, ROW_EST, ROW_EWMA,
                                    ROW_LOADS, ROW_PROBS, lane_sum,
                                    permute_from_sorted, permute_to_sorted,
                                    rank_desc, recursive_average_bounds,
                                    window_decrements)

_BIG = 3.4e38  # padding-lane load: never selected, never drained


def window_lanes(window_size: int) -> int:
    """Lanes one window occupies in the kernel's request layout: the
    window rounded up to whole vregs, so every window starts at a lane
    offset Mosaic can prove to be a multiple of 128."""
    return -(-window_size // LANES) * LANES


def _lcg(rng):
    return rng * jnp.uint32(LCG_A) + jnp.uint32(LCG_C)


def _lcg_mod(rng, n: int):
    return jax.lax.rem((rng >> jnp.uint32(8)).astype(jnp.int32)
                       & jnp.int32(0x7FFFFFFF), n)


def _argmin_first(x, lane):
    """Index of the FIRST minimum along the last axis, ``(..., 1)`` int32
    — `jnp.argmin`'s tie rule spelled out.  Ties are common (servers
    drained to zero load score equal), and Mosaic's argmin reduction
    does not promise the lowest index among equal minima."""
    mn = jnp.min(x, axis=-1, keepdims=True)
    return jnp.min(jnp.where(x == mn, lane, x.shape[-1]), axis=-1,
                   keepdims=True)


def _sched_stream_kernel(objs_ref, lens_ref, valid_ref, table_ref, seed_ref,
                         rates_ref, dec_ref, choices_ref, lats_ref,
                         final_table_ref, wloads_ref, metrics_ref, tbl, *,
                         n_windows: int, window_size: int, n_servers: int,
                         m_pad: int, t_tile: int, client_tile: int,
                         threshold: float, lam: float, alpha: float,
                         window_dt: float, policy: str, observe: bool,
                         renorm: bool, nltr_n: int, probe_choices: int,
                         ablate: int = 0):
    """One program instance of the stream kernel.

    Per-stream refs are ``(s_tile, ...)`` with ``s_tile = t_tile *
    client_tile`` (``client_tile == 1`` is the trial-grid form); the
    request refs are ``(s_tile, n_windows * ws_pad)`` in the padded
    window layout; the per-trial rate/decrement refs are ``(t_tile,
    n_windows, m_pad)``; ``tbl`` is the ``(N_ROWS, s_tile, m_pad)``
    table scratch.

    ``ablate`` drops whole window phases for DIFFERENTIAL per-phase
    profiling (DESIGN.md §16): 0 = the full kernel; 1 = skip the fused
    metrics reduction; 2 = also skip the per-request step loop; 3 =
    also skip the window-start sort/plan.  Levels are cumulative so
    every retained phase still sees the inputs it would normally see.
    Ablated outputs are NOT contract-bearing (choices/latencies/metrics
    are undefined past the dropped phase) — the levels exist only so
    `benchmarks/sched_perf.py` can attribute the kernel's wall time
    phase by phase via timing differences."""
    m = n_servers
    ws_pad = window_lanes(window_size)
    s_tile = t_tile * client_tile
    do_metrics = ablate < 1
    do_steps = ablate < 2
    do_plan = ablate < 3

    def req_read(ref, w):
        return ref[:, pl.ds(pl.multiple_of(w * ws_pad, LANES), ws_pad)]

    def req_write(ref, w, val):
        ref[:, pl.ds(pl.multiple_of(w * ws_pad, LANES), ws_pad)] = val

    def trial_row(ref, w):
        """(s_tile, m_pad) per-stream row of a per-TRIAL operand."""
        r = ref[:, pl.ds(w, 1), :][:, 0, :]             # (t_tile, m_pad)
        if client_tile == 1:
            return r
        # stream s belongs to trial s // client_tile: broadcast each
        # trial's row over its client sublanes with exact selects
        sid = jax.lax.broadcasted_iota(jnp.int32, (s_tile, 1), 0)
        out = jnp.broadcast_to(r[0:1], (s_tile, m_pad))
        for t in range(1, t_tile):
            out = jnp.where(sid >= t * client_tile, r[t:t + 1], out)
        return out

    intab = table_ref[...]                      # (s_tile, 4, m_pad)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, m_pad), 1)
    lv = lane < m                               # valid (non-padding) lanes
    ws_lane = jax.lax.broadcasted_iota(jnp.int32, (1, ws_pad), 1)

    # --- pin the packed log stack in VMEM scratch --------------------------
    # tbl is (N_ROWS, s_tile, m_pad): tbl[row] is this tile's streams' row,
    # one (s_tile, m_pad) tile per op below (streams ride the sublanes).
    tbl[ROW_LOADS] = jnp.where(lv, intab[:, ROW_LOADS, :], _BIG)
    tbl[ROW_PROBS] = jnp.where(lv, intab[:, ROW_PROBS, :], 0.0)
    tbl[ROW_EWMA] = jnp.where(lv, intab[:, ROW_EWMA, :], 0.0)
    tbl[ROW_EST] = jnp.where(lv, intab[:, ROW_EST, :], 1.0)

    def pick(rows, onehot):
        """Extract rows[onehot] per stream without gather (masked sum)."""
        return jnp.sum(jnp.where(onehot, rows, 0.0), axis=-1, keepdims=True)

    def window_body(w, carry):
        rng, mk, lsum, lmax, nval = carry
        cur_rates = jnp.where(lv, trial_row(rates_ref, w), 1.0)
        sort_policy = policy in ("mlml", "nltr")
        # the window's requests, read once: (s_tile, ws_pad) each
        obj_w = req_read(objs_ref, w)
        len_w = req_read(lens_ref, w)
        val_w = req_read(valid_ref, w) != 0

        if policy in PLAN_POLICIES and do_plan:
            # Window-start plan (DESIGN.md §13, §19): rank servers by
            # probability desc with ONE all-pairs comparison, then land
            # the server ids in rank order with a single permutation
            # apply.  Padding lanes get -inf keys so positions [0, M)
            # are exactly the engine's stable argsort(-probs)
            # permutation — same strict total order, no sort network.
            rank_srv, _ = rank_desc(
                tbl[ROW_PROBS], valid=jnp.broadcast_to(lv, (s_tile, m_pad)))
            (order_srv,) = permute_to_sorted(
                rank_srv, (jnp.broadcast_to(lane, (s_tile, m_pad)),))
            srt_lane = jax.lax.broadcasted_iota(
                jnp.int32, (1, order_srv.shape[-1]), 1)

        def server_at(p):
            """Server id at sorted position p (one-hot masked sum)."""
            return jnp.sum(jnp.where(srt_lane == p, order_srv, 0), axis=-1,
                           keepdims=True).astype(jnp.int32)

        if sort_policy and do_plan:
            # MLML/nLTR process the window's requests in length-desc
            # order (DESIGN.md §13): rank the request block with one
            # all-pairs comparison, land obj/len/valid in sorted order
            # with one permutation apply, and loop over POSITIONS.
            rank_req, mkeys = rank_desc(len_w, valid=val_w)
            obj_s, len_s, val_s = permute_to_sorted(
                rank_req, (obj_w, len_w, val_w.astype(jnp.int32)))
            if policy == "nltr":
                nvalid = jnp.sum(val_w.astype(jnp.int32), axis=-1,
                                 keepdims=True)
                # sorted keys (-inf at invalid) for the section bounds;
                # lane_sum's zero-padded halving tree is width-
                # independent, so the bounds match the engine's
                skeys = permute_to_sorted(rank_req, (mkeys,))[0]
                bounds = recursive_average_bounds(skeys, nvalid, nltr_n)
                sec_size = max(m // 2 ** nltr_n, 1)
                n_sections = 2 ** nltr_n
        else:
            # processing order == request order
            obj_s, len_s, val_s = obj_w, len_w, val_w.astype(jnp.int32)

        def schedule_one(j, obj, ln, v, rng):
            """Selection + guard + Eq. (1)-(3)/feedback for one request per
            stream; mutates the VMEM table, returns (choose, lat, latv,
            rng).  ``j`` is the PROCESSING position in the window (==
            request position except for the sorted policies)."""
            loads = tbl[ROW_LOADS]
            probs = tbl[ROW_PROBS]
            est = tbl[ROW_EST]
            default = jax.lax.rem(obj, m)

            # -- target selection (policy_core decision math) --------------
            if policy == "rr":
                target = default
            elif policy == "minload":
                target = _argmin_first(loads, lane)
            elif policy == "ect":
                target = _argmin_first((loads + ln) / est, lane)
            elif policy == "mlml":
                # j-th longest request -> j-th lightest server (Alg. 1)
                target = server_at(jnp.reshape(jax.lax.rem(j, m), (1, 1)))
            elif policy == "nltr":
                # request section from the recursive-average bounds, two
                # LCG draws inside the matching server section (Alg. 3)
                sec = jnp.sum((j >= bounds).astype(jnp.int32), axis=-1,
                              keepdims=True)
                sec = jnp.clip(sec, 0, n_sections - 1)
                lo = sec * sec_size
                r1 = _lcg(rng)
                r2 = _lcg(r1)
                rng = r2
                c1 = server_at(lo + _lcg_mod(r1, sec_size))
                c2 = server_at(lo + _lcg_mod(r2, sec_size))
                l1 = pick(loads, lane == c1)
                l2 = pick(loads, lane == c2)
                target = jnp.where(l1 <= l2, c1, c2).astype(jnp.int32)
            elif policy == "two_choice":
                # SC'14 baseline: default + LCG-random candidates, first
                # min by live load (matches jnp.argmin's tie rule)
                target = default
                best_l = pick(loads, lane == default)
                for _ in range(probe_choices - 1):
                    rng = _lcg(rng)
                    c = _lcg_mod(rng, m)
                    l_c = pick(loads, lane == c)
                    better = l_c < best_l
                    target = jnp.where(better, c, target).astype(jnp.int32)
                    best_l = jnp.where(better, l_c, best_l)
            elif policy in ("two_random", "trh"):
                r1 = _lcg(rng)
                r2 = _lcg(r1)
                rng = r2
                if policy == "two_random":
                    c1 = _lcg_mod(r1, m)
                    c2 = _lcg_mod(r2, m)
                else:  # trh: two positions in the lightest half
                    half = max(m // 2, 1)
                    c1 = server_at(_lcg_mod(r1, half))
                    c2 = server_at(_lcg_mod(r2, half))
                l1 = pick(loads, lane == c1)
                l2 = pick(loads, lane == c2)
                target = jnp.where(l1 <= l2, c1, c2).astype(jnp.int32)
            else:  # pragma: no cover
                raise ValueError(policy)

            # -- redirect-threshold guard (§3.4.1; rr has no guard) --------
            if policy == "rr":
                choose = default
            else:
                l_def = pick(loads, lane == default)
                l_tgt = pick(loads, lane == target)
                if policy == "ect":
                    # rate-aware benefit in expected seconds, on EST rates
                    r_def = pick(est, lane == default)
                    r_tgt = pick(est, lane == target)
                    benefit = (l_def + ln) / r_def - (l_tgt + ln) / r_tgt
                else:
                    benefit = l_def - l_tgt
                choose = jnp.where(benefit > threshold, target,
                                   default).astype(jnp.int32)

            # -- Eq. (1)-(3) one-hot updates (masked on padding rows) ------
            onehot = lane == choose                          # (s, m_pad)
            upd = onehot & v
            new_loads = jnp.where(upd, loads + ln, loads)    # Eq. (1)
            tbl[ROW_LOADS] = new_loads
            p_i = pick(probs, onehot)
            l_i = pick(new_loads, onehot)
            e = jnp.exp(-l_i / lam)
            decayed = p_i * e                                # Eq. (2)
            delta = p_i * (1.0 - e) / (m - 1)                # Eq. (3)
            new_probs = jnp.where(onehot, decayed,
                                  jnp.where(lv, probs + delta, 0.0))
            tbl[ROW_PROBS] = jnp.where(v, new_probs, probs)

            # -- estimated completion latency + completion feedback --------
            l_after = pick(new_loads, onehot)
            rate_c = pick(cur_rates, onehot)                 # TRUE rate
            lat = l_after / jnp.maximum(rate_c, 1e-6)
            latv = jnp.where(v, lat, 0.0)
            if observe:
                # effective MB/s this request will see -> ewma row; est
                # row re-derived from observations ONLY (stale view).
                mbps = ln / jnp.maximum(lat, 1e-9)
                ewma = tbl[ROW_EWMA]
                old = pick(ewma, onehot)
                new = jnp.where(old == 0.0, mbps,
                                # contract-ok: CC-FMA EWMA row is 1e-6-soft (§9)
                                (1 - alpha) * old + alpha * mbps)
                new_ewma = jnp.where(upd, new, ewma)
                tbl[ROW_EWMA] = new_ewma
                dflt = jnp.maximum(jnp.max(new_ewma, axis=-1, keepdims=True),
                                   1.0)
                tbl[ROW_EST] = jnp.where(new_ewma > 0, new_ewma, dflt)
            return choose, lat, latv, rng

        wopen = w.astype(jnp.float32) * jnp.float32(window_dt)

        if not do_steps:
            # ablate >= 2: the window keeps its renorm/drain bookkeeping
            # but schedules nothing — the timing delta vs level 1 is the
            # step loop's cost.
            carry = (rng, mk, lsum, lmax, nval)
        else:
            def req_body(j, carry):
                rng, ch_acc, lat_acc, mk, lsum, lmax, nval = carry
                sel = ws_lane == j              # PROCESSING position j
                obj = jnp.sum(jnp.where(sel, obj_s, 0), axis=-1,
                              keepdims=True)
                ln = jnp.sum(jnp.where(sel, len_s, 0.0), axis=-1,
                             keepdims=True)
                v = jnp.sum(jnp.where(sel, val_s, 0), axis=-1,
                            keepdims=True) != 0
                choose, lat, latv, rng = schedule_one(j, obj, ln, v, rng)
                ch_acc = jnp.where(sel, choose, ch_acc)
                lat_acc = jnp.where(sel, latv, lat_acc)
                if not sort_policy:
                    # fused metric accumulators (stream_metrics twin)
                    mk = jnp.where(v, jnp.maximum(mk, wopen + lat), mk)
                    lsum = lsum + latv
                    lmax = jnp.maximum(lmax, latv)
                    nval = nval + jnp.where(v, 1.0, 0.0)
                return rng, ch_acc, lat_acc, mk, lsum, lmax, nval

            rng, ch_acc, lat_acc, mk, lsum, lmax, nval = jax.lax.fori_loop(
                0, window_size, req_body,
                (rng, jnp.zeros((s_tile, ws_pad), jnp.int32),
                 jnp.zeros((s_tile, ws_pad), jnp.float32),
                 mk, lsum, lmax, nval),
                unroll=False)
            if sort_policy:
                # decisions accumulated in SORTED order; ONE inverse
                # apply moves everything back at once (§13)
                ch_acc, lat_acc = permute_from_sorted(rank_req,
                                                      (ch_acc, lat_acc))
                # fused metrics in ORIGINAL request order (stream_metrics
                # twin).  makespan/lat_max are exact order-free f32
                # maxima and the valid count is integer-exact under any
                # summation tree, so they collapse to vectorized masked
                # reductions; ONLY the latency sum keeps the host twin's
                # sequential per-request float-add chain (f32 adds do
                # not reassociate).
                mk = jnp.maximum(mk, jnp.max(
                    jnp.where(val_w, wopen + lat_acc, -_BIG), axis=-1,
                    keepdims=True))
                lmax = jnp.maximum(lmax, jnp.max(lat_acc, axis=-1,
                                                 keepdims=True))
                nval = nval + jnp.sum(jnp.where(val_w, 1.0, 0.0), axis=-1,
                                      keepdims=True)

                def lsum_body(j, acc):
                    return acc + jnp.sum(
                        jnp.where(ws_lane == j, lat_acc, 0.0), axis=-1,
                        keepdims=True)

                lsum = jax.lax.fori_loop(0, window_size, lsum_body, lsum,
                                         unroll=False)
            req_write(choices_ref, w, ch_acc)
            req_write(lats_ref, w, lat_acc)
            carry = (rng, mk, lsum, lmax, nval)

        # -- window close: renormalize probs, drain queues (advance_time) --
        if renorm:
            # lane_sum: the shared explicit halving tree (§9 parity)
            p = jnp.clip(tbl[ROW_PROBS], 0.0)
            tbl[ROW_PROBS] = p / lane_sum(p)
        if window_dt:
            # Drain decrements arrive PRE-MULTIPLIED (window_decrements,
            # materialized as a kernel operand): an in-body rates*dt next
            # to this subtract gets FMA-contracted in some lowering
            # contexts but not others (observed tile-dependent), a 1-ulp
            # drift that breaks the §9 parity contract.  A bare subtract
            # rounds identically everywhere.
            loads = tbl[ROW_LOADS]
            dec = jnp.where(lv, trial_row(dec_ref, w), 0.0)
            drained = jnp.maximum(loads - dec, 0.0)
            tbl[ROW_LOADS] = jnp.where(lv, drained, _BIG)
        wloads_ref[:, pl.ds(w, 1), :] = jnp.where(
            lv, tbl[ROW_LOADS], 0.0)[:, None, :]
        return carry

    seed = seed_ref[...].astype(jnp.uint32)                  # (s, 1)
    zero = jnp.zeros((s_tile, 1), jnp.float32)
    _, mk, lsum, lmax, nval = jax.lax.fori_loop(
        0, n_windows, window_body, (seed, zero, zero, zero, zero),
        unroll=False)
    zero_pad = jnp.broadcast_to(~lv, (s_tile, m_pad))
    for row in range(N_ROWS):
        final_table_ref[:, row, :] = jnp.where(zero_pad, 0.0, tbl[row])

    # -- fused metrics: reduce the VMEM-resident latency block -------------
    # (policy_core.stream_metrics / nearest_rank_p99 are the bit-exact
    # host twins — keep the float ops in lockstep with them.  The
    # window padding lanes are invalid with zero latency, so they drop
    # out of every reduction.)
    if not do_metrics:
        metrics_ref[...] = jnp.zeros((s_tile, MET_PAD), jnp.float32)
        return
    lats_all = lats_ref[...]                                 # (s, N_pad)
    val_all = valid_ref[...] != 0
    mlane = jax.lax.broadcasted_iota(jnp.int32, (1, MET_PAD), 1)
    k = jnp.ceil(jnp.float32(P99_Q) * nval)
    lo = jnp.full((s_tile, 1), -1.0, jnp.float32)
    hi = lmax

    def bisect(_, lo_hi):
        lo, hi = lo_hi
        mid = jnp.float32(0.5) * (lo + hi)
        cnt = jnp.sum(jnp.where(val_all & (lats_all <= mid), 1.0, 0.0),
                      axis=-1, keepdims=True)
        go_hi = cnt >= k
        return jnp.where(go_hi, lo, mid), jnp.where(go_hi, mid, hi)

    lo, _ = jax.lax.fori_loop(0, P99_BISECT_ITERS, bisect, (lo, hi))
    p99 = jnp.min(jnp.where(val_all & (lats_all > lo), lats_all, _BIG),
                  axis=-1, keepdims=True)
    p99 = jnp.where(nval > 0, p99, 0.0)
    metrics_ref[...] = (jnp.where(mlane == MET_MAKESPAN, mk, 0.0)
                        + jnp.where(mlane == MET_P99, p99, 0.0)
                        + jnp.where(mlane == MET_LAT_SUM, lsum, 0.0)
                        + jnp.where(mlane == MET_LAT_MAX, lmax, 0.0)
                        + jnp.where(mlane == MET_N_VALID, nval, 0.0))


def _to_window_layout(x, n_win: int, window_size: int):
    """(..., n_win * window_size) -> (..., n_win * ws_pad): each window
    padded with zero (= invalid, inert) lanes to whole vregs."""
    ws_pad = window_lanes(window_size)
    if ws_pad == window_size:
        return x
    lead = x.shape[:-1]
    x = x.reshape(lead + (n_win, window_size))
    x = jnp.pad(x, [(0, 0)] * (len(lead) + 1)
                + [(0, ws_pad - window_size)])
    return x.reshape(lead + (n_win * ws_pad,))


def _from_window_layout(x, n_win: int, window_size: int):
    """Inverse of :func:`_to_window_layout` (drops the padding lanes)."""
    ws_pad = window_lanes(window_size)
    if ws_pad == window_size:
        return x
    lead = x.shape[:-1]
    x = x.reshape(lead + (n_win, ws_pad))[..., :window_size]
    return x.reshape(lead + (n_win * window_size,))


def _to_blocks(x, tt: int, ct: int):
    """(T, C, ...) -> (T/tt, C/ct, tt*ct, ...): the streams of each
    (trial tile, client tile) block made contiguous, trial-major."""
    t, c = x.shape[:2]
    rest = x.shape[2:]
    x = x.reshape((t // tt, tt, c // ct, ct) + rest)
    x = jnp.moveaxis(x, 2, 1)
    return x.reshape((t // tt, c // ct, tt * ct) + rest)


def _from_blocks(x, tt: int, ct: int):
    """Inverse of :func:`_to_blocks`."""
    nt, nc = x.shape[:2]
    rest = x.shape[3:]
    x = x.reshape((nt, nc, tt, ct) + rest)
    x = jnp.moveaxis(x, 1, 2)
    return x.reshape((nt * tt, nc * ct) + rest)


def _stream_blocks_call(object_ids, lengths, valid, tables, seeds, win_rates,
                        *, trial_tile: int, client_tile: int,
                        window_size: int, interpret: bool, **kw):
    """The ONE ``pallas_call``: ``(T, C, ...)`` per-stream operands,
    ``(T, W, M_pad)`` per-trial rates, grid ``(T / tt, C / ct)``.
    Returns the per-stream (choices, latencies, final_tables,
    window_loads, metrics), each ``(T, C, ...)``."""
    t, c, n = object_ids.shape
    m_pad = tables.shape[-1]
    n_win = win_rates.shape[1]
    if n != n_win * window_size:
        raise ValueError(f"stream length {n} != {n_win} windows x "
                         f"{window_size}")
    tt, ct = trial_tile, client_tile
    if t % tt or c % ct:
        raise ValueError(f"(T, C)=({t}, {c}) must be multiples of the "
                         f"tiles ({tt}, {ct})")
    s = tt * ct
    n_lanes = n_win * window_lanes(window_size)
    # drain decrements: pre-multiplied OUTSIDE the kernel (§9 FMA note)
    win_dec = window_decrements(win_rates, kw["window_dt"]).astype(
        jnp.float32)

    def streams(x):
        return _to_blocks(x, tt, ct)

    def stream_spec(*shape):
        zeros = (0,) * (1 + len(shape))
        return pl.BlockSpec((pl.Squeezed(), pl.Squeezed(), s) + shape,
                            lambda i, j: (i, j) + zeros)

    trial_spec = pl.BlockSpec((tt, n_win, m_pad), lambda i, j: (i, 0, 0))
    req = [streams(_to_window_layout(a, n_win, window_size))
           for a in (object_ids, lengths, valid)]
    kernel = functools.partial(
        _sched_stream_kernel, n_windows=n_win, window_size=window_size,
        m_pad=m_pad, t_tile=tt, client_tile=ct, **kw)
    blocks = (t // tt, c // ct, s)
    outs = pl.pallas_call(
        kernel,
        grid=(t // tt, c // ct),
        in_specs=[stream_spec(n_lanes)] * 3 + [
            stream_spec(N_ROWS, m_pad), stream_spec(1), trial_spec,
            trial_spec],
        out_specs=[stream_spec(n_lanes), stream_spec(n_lanes),
                   stream_spec(N_ROWS, m_pad), stream_spec(n_win, m_pad),
                   stream_spec(MET_PAD)],
        out_shape=[
            jax.ShapeDtypeStruct(blocks + (n_lanes,), jnp.int32),
            jax.ShapeDtypeStruct(blocks + (n_lanes,), jnp.float32),
            jax.ShapeDtypeStruct(blocks + (N_ROWS, m_pad), jnp.float32),
            jax.ShapeDtypeStruct(blocks + (n_win, m_pad), jnp.float32),
            jax.ShapeDtypeStruct(blocks + (MET_PAD,), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((N_ROWS, s, m_pad), jnp.float32)],
        interpret=interpret,
    )(*req, streams(tables), streams(seeds[..., None]), win_rates, win_dec)
    choices, lats, ftab, wloads, metrics = (_from_blocks(o, tt, ct)
                                            for o in outs)
    return (_from_window_layout(choices, n_win, window_size),
            _from_window_layout(lats, n_win, window_size),
            ftab, wloads, metrics)


def sched_stream_call(object_ids: jax.Array, lengths: jax.Array,
                      valid: jax.Array, tables: jax.Array, seeds: jax.Array,
                      win_rates: jax.Array, *, n_servers: int,
                      window_size: int, threshold: float, lam: float,
                      alpha: float, window_dt: float, policy: str,
                      observe: bool, renorm: bool, trial_tile: int = 1,
                      nltr_n: int = 2, probe_choices: int = 2,
                      ablate: int = 0, interpret: bool = False):
    """Temporal stream kernel over T independent streams (clients/trials).

    ``ablate`` drops trailing window phases for differential profiling
    (see `_sched_stream_kernel`); outputs past the dropped phase are
    undefined, so nonzero levels are for timing only.

    object_ids/lengths/valid: (T, N) with N = W * window_size;
    tables: (T, 4, M_pad) packed log tensors; seeds: (T, 1) uint32;
    win_rates: (T, W, M_pad) TRUE service rates per window.  T must be a
    multiple of ``trial_tile``; each of the ``T / trial_tile`` program
    instances runs its tile of streams vectorized over VMEM sublanes.

    Returns (choices (T, N) int32, latencies (T, N) f32,
    final_tables (T, 4, M_pad) f32, window_loads (T, W, M_pad) f32,
    metrics (T, MET_PAD) f32 in `policy_core.MET_*` lane order).
    """
    outs = _stream_blocks_call(
        object_ids[:, None], lengths[:, None], valid[:, None],
        tables[:, None], seeds.reshape(-1, 1), win_rates,
        trial_tile=trial_tile, client_tile=1, window_size=window_size,
        interpret=interpret, n_servers=n_servers, threshold=threshold,
        lam=lam, alpha=alpha, window_dt=window_dt, policy=policy,
        observe=observe, renorm=renorm, nltr_n=nltr_n,
        probe_choices=probe_choices, ablate=ablate)
    return tuple(o[:, 0] for o in outs)


def sched_stream_grid_call(object_ids: jax.Array, lengths: jax.Array,
                           valid: jax.Array, tables: jax.Array,
                           seeds: jax.Array, win_rates: jax.Array, *,
                           n_servers: int, window_size: int, threshold: float,
                           lam: float, alpha: float, window_dt: float,
                           policy: str, observe: bool, renorm: bool,
                           trial_tile: int = 1, client_tile: int = 1,
                           nltr_n: int = 2, probe_choices: int = 2,
                           interpret: bool = False):
    """2-D (trials × clients) grid form of the stream kernel (§11).

    object_ids/lengths/valid: (T, C, N) per-stream request slices (N =
    W * window_size); tables: (T, C, 4, M_pad) private log tensors;
    seeds: (T, C) uint32; win_rates: (T, W, M_pad) per-TRIAL true rates
    (all of a trial's clients share its trace — broadcast over the
    client sublanes in-VMEM, never materialized per client).  T and C
    must be multiples of ``trial_tile`` / ``client_tile``; the grid runs
    ``(T / tt, C / ct)`` program instances, each vectorizing its
    ``tt * ct`` streams over VMEM sublanes.

    Returns the per-stream (choices (T, C, N) int32, latencies (T, C, N)
    f32, final_tables (T, C, 4, M_pad) f32, window_loads (T, C, W,
    M_pad) f32, metrics (T, C, MET_PAD) f32); the cross-client merges
    are `ops.sched_stream_grid`'s.
    """
    return _stream_blocks_call(
        object_ids, lengths, valid, tables, seeds, win_rates,
        trial_tile=trial_tile, client_tile=client_tile,
        window_size=window_size, interpret=interpret, n_servers=n_servers,
        threshold=threshold, lam=lam, alpha=alpha, window_dt=window_dt,
        policy=policy, observe=observe, renorm=renorm, nltr_n=nltr_n,
        probe_choices=probe_choices)


def sched_select_call(object_ids: jax.Array, lengths: jax.Array,
                      init_loads: jax.Array, seeds: jax.Array, *,
                      n_servers: int, threshold: float, lam: float,
                      policy: str, interpret: bool = False):
    """Legacy single-window entry (paper's static-load model).

    object_ids/lengths: (C, N); init_loads: (C, M_pad); seeds: (C, 1).
    Returns (choices (C, N) int32, final_loads (C, M_pad) f32).  This is
    the temporal kernel degenerated to one window: uniform probability
    prior, no observations, no drain, no renormalization — bit-identical
    to the pre-refactor kernel.
    """
    c, n = object_ids.shape
    m_pad = init_loads.shape[1]
    m = n_servers
    probs = jnp.full((c, m_pad), 1.0 / m, jnp.float32)
    tables = jnp.stack([
        init_loads.astype(jnp.float32),
        probs,
        jnp.zeros((c, m_pad), jnp.float32),
        jnp.ones((c, m_pad), jnp.float32),
    ], axis=1)                                    # (C, 4, m_pad)
    valid = jnp.ones((c, n), jnp.int32)
    rates = jnp.ones((c, 1, m_pad), jnp.float32)  # one window, unit rates
    choices, _, final_tables, _, _ = sched_stream_call(
        object_ids, lengths, valid, tables, seeds, rates, n_servers=m,
        window_size=n, threshold=threshold, lam=lam, alpha=0.25,
        window_dt=0.0, policy=policy, observe=False, renorm=False,
        interpret=interpret)
    return choices, final_tables[:, ROW_LOADS, :]
