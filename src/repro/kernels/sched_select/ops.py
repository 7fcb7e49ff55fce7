"""Jit'd wrappers for the sched_select kernels (auto-interpret on CPU).

Four entry points:

* :func:`sched_select` — the legacy single-window static-load form
  (minload / two_random), kept bit-identical to the seed kernel;
* :func:`sched_stream` — the temporal stream form: a whole
  ``engine.run_stream`` trace (windows, drain, completion feedback) as
  ONE ``pallas_call`` over the packed ``(4, M)`` log tensor.  This is
  what ``engine.run_stream(backend="kernel")`` dispatches to.
* :func:`sched_stream_batch` — the TRIAL-GRID form (DESIGN.md §9): a
  whole T-trial Monte-Carlo sweep as ONE ``pallas_call`` with
  ``grid = (ceil(T / trial_tile),)``; each program instance runs
  ``trial_tile`` trials vectorized over VMEM sublanes and reduces its
  fused per-trial metrics in-VMEM.  ``engine.run_stream_batch`` (and
  through it ``simulate.run_trials(backend="kernel")``) dispatches here.
* :func:`sched_stream_grid` — the 2-D (TRIALS × CLIENTS) grid form
  (DESIGN.md §11): the per_client contention model's whole sweep — T
  trials × C private-log clients — as ONE ``pallas_call`` with
  ``grid = (ceil(T / trial_tile), ceil(C / client_tile))``; the
  cross-client merges fold the kernel's per-stream outputs with the
  `policy_core` twins.  ``engine.run_stream_batch`` with
  a ``(T, C)`` leading batch (and through it ``simulate.run_trials(
  backend="kernel", client_model="per_client")``) dispatches here.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.policy_core import (N_METRICS, client_stream_metrics,
                                    init_table, masked_client_mean,
                                    masked_client_sum, resolve_client_tile,
                                    resolve_trial_tile)
from repro.kernels.sched_select.kernel import (sched_select_call,
                                               sched_stream_call,
                                               sched_stream_grid_call)
from repro.tune import profile as tune_profile

POLICIES = ("minload", "two_random", "ect", "trh", "rr", "two_choice",
            "mlml", "nltr")
# the paper's policies that need per-window sorts — in-VMEM since
# DESIGN.md §10, on the §13 permutation-apply fast path (one all-pairs
# rank + a constant number of permutation applies per window, no sort
# network) since PR 7
SORT_POLICIES = ("mlml", "nltr")
# policies available through the legacy static entry point
STATIC_POLICIES = ("minload", "two_random")


def _check_policy(policy: str, n_servers: int, nltr_n: int) -> None:
    if policy not in POLICIES:
        raise ValueError(f"kernel policy must be one of {POLICIES}")
    if policy == "nltr" and 2 ** nltr_n > n_servers:
        raise ValueError(
            f"nltr needs 2**nltr_n <= n_servers: nltr_n={nltr_n} gives "
            f"K={2 ** nltr_n} sections for n_servers={n_servers}")


def _pad_servers(m: int) -> int:
    return max(-(-m // 128) * 128, 128)


def _auto_interpret(interpret: Optional[bool]) -> bool:
    return jax.default_backend() == "cpu" if interpret is None else interpret


@functools.partial(jax.jit, static_argnames=("n_servers", "threshold",
                                             "lam", "policy", "interpret"))
def sched_select(object_ids: jax.Array, lengths: jax.Array,
                 init_loads: jax.Array, seeds: jax.Array, *,
                 n_servers: int, threshold: float = 0.0, lam: float = 32.0,
                 policy: str = "minload",
                 interpret: Optional[bool] = None
                 ) -> Tuple[jax.Array, jax.Array]:
    """Schedule request streams for C independent clients (static model).

    object_ids/lengths: (C, N); init_loads: (C, M) true server loads known
    to each client's log; seeds: (C,) uint32.  Returns (choices (C, N),
    final_loads (C, M)).
    """
    if policy not in STATIC_POLICIES:
        raise ValueError(f"kernel policy must be one of {STATIC_POLICIES}")
    interpret = _auto_interpret(interpret)
    c, n = object_ids.shape
    m = init_loads.shape[1]
    m_pad = _pad_servers(m)
    loads_p = jnp.pad(init_loads.astype(jnp.float32),
                      ((0, 0), (0, m_pad - m)))
    choices, final_loads = sched_select_call(
        object_ids.astype(jnp.int32), lengths.astype(jnp.float32),
        loads_p, seeds.reshape(c, 1).astype(jnp.uint32),
        n_servers=n_servers, threshold=threshold, lam=lam, policy=policy,
        interpret=interpret)
    return choices, final_loads[:, :m]


@functools.partial(jax.jit, static_argnames=("n_servers", "window_size",
                                             "threshold", "lam", "alpha",
                                             "window_dt", "policy",
                                             "observe", "renorm", "nltr_n",
                                             "probe_choices", "interpret"))
def sched_stream(object_ids: jax.Array, lengths: jax.Array,
                 valid: jax.Array, table: jax.Array, seed: jax.Array,
                 win_rates: jax.Array, *, n_servers: int, window_size: int,
                 threshold: float = 0.0, lam: float = 32.0,
                 alpha: float = 0.25, window_dt: float = 0.0,
                 policy: str = "ect", observe: bool = True,
                 renorm: bool = True, nltr_n: int = 2,
                 probe_choices: int = 2, interpret: Optional[bool] = None
                 ) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Temporal kernel: one client's whole windowed stream in VMEM.

    object_ids/lengths/valid: (N,) with N = W * window_size (padding rows
    ``valid == False``); table: the (4, M) packed log tensor
    (`SchedState.log`); seed: () uint32 LCG state; win_rates: (W, M) TRUE
    service rates at each window open (drain + latency reporting — the
    decision path only ever reads the table's est row).

    Returns (choices (N,), latencies (N,), final_table (4, M),
    window_loads (W, M) post-drain snapshots).

    Batched form: pass (C, N) / (C, 4, M) / (C,) / (C, W, M) arrays and
    every output gains the leading client axis (grid = clients).
    """
    _check_policy(policy, n_servers, nltr_n)
    interpret = _auto_interpret(interpret)
    single = object_ids.ndim == 1
    if single:
        object_ids, lengths, valid = (object_ids[None], lengths[None],
                                      valid[None])
        table, seed, win_rates = table[None], seed[None], win_rates[None]
    c, n = object_ids.shape
    m = table.shape[-1]
    n_win = win_rates.shape[1]
    m_pad = _pad_servers(m)
    pad = ((0, 0), (0, 0), (0, m_pad - m))
    tables_p = jnp.pad(table.astype(jnp.float32), pad)
    rates_p = jnp.pad(win_rates.astype(jnp.float32), pad)
    choices, lats, ftab, wloads, _ = sched_stream_call(
        object_ids.astype(jnp.int32), lengths.astype(jnp.float32),
        valid.astype(jnp.int32), tables_p,
        seed.reshape(c, 1).astype(jnp.uint32), rates_p,
        n_servers=n_servers, window_size=window_size, threshold=threshold,
        lam=lam, alpha=alpha, window_dt=window_dt, policy=policy,
        observe=observe, renorm=renorm, nltr_n=nltr_n,
        probe_choices=probe_choices, interpret=interpret)
    ftab = ftab[:, :, :m]
    wloads = wloads[:, :, :m]
    if single:
        return choices[0], lats[0], ftab[0], wloads[0]
    return choices, lats, ftab, wloads


@functools.partial(jax.jit, static_argnames=("n_servers", "window_size",
                                             "threshold", "lam", "alpha",
                                             "window_dt", "policy",
                                             "observe", "renorm",
                                             "trial_tile", "nltr_n",
                                             "probe_choices", "ablate",
                                             "interpret"))
def sched_stream_batch(object_ids: jax.Array, lengths: jax.Array,
                       valid: jax.Array, tables: jax.Array, seeds: jax.Array,
                       win_rates: jax.Array, *, n_servers: int,
                       window_size: int, threshold: float = 0.0,
                       lam: float = 32.0, alpha: float = 0.25,
                       window_dt: float = 0.0, policy: str = "ect",
                       observe: bool = True, renorm: bool = True,
                       trial_tile: Optional[int] = None,
                       nltr_n: int = 2, probe_choices: int = 2,
                       ablate: int = 0,
                       interpret: Optional[bool] = None
                       ) -> Tuple[jax.Array, jax.Array, jax.Array,
                                  jax.Array, jax.Array]:
    """Trial-grid kernel: T whole windowed streams as ONE ``pallas_call``.

    object_ids/lengths/valid: (T, N) per-trial streams (N = W *
    window_size, padding rows ``valid == False``); tables: (T, 4, M)
    packed log tensors; seeds: (T,) uint32 LCG states; win_rates:
    (T, W, M) TRUE per-window service rates.  T is padded up to a
    multiple of ``trial_tile`` with inert trials (all-invalid requests,
    fresh tables, unit rates) and the grid runs ``ceil(T / trial_tile)``
    program instances, each vectorizing its tile of trials over VMEM
    sublanes (unset, the tile follows the row depth:
    `policy_core.resolve_trial_tile`, DESIGN.md §20) — bit-exact per
    trial vs. mapping :func:`sched_stream` sequentially (asserted in
    tests/test_kernels.py).

    Returns (choices (T, N) int32, latencies (T, N) f32, final_tables
    (T, 4, M) f32, window_loads (T, W, M) f32, metrics (T, N_METRICS)
    f32 in `policy_core.MET_*` order — the fused in-VMEM reduction).

    ``ablate`` > 0 drops trailing kernel phases (1 = fused metrics, 2 =
    + step loop, 3 = + window-start plan) for DIFFERENTIAL per-phase
    profiling (DESIGN.md §16); ablated outputs are zeros past the
    dropped phase, so nonzero levels are for timing only.
    """
    _check_policy(policy, n_servers, nltr_n)
    interpret = _auto_interpret(interpret)
    t, n = object_ids.shape
    m = tables.shape[-1]
    tile = resolve_trial_tile(t, trial_tile, n_servers=m, n_requests=n,
                              window_size=window_size, policy=policy)
    t_pad = -(-t // tile) * tile
    m_pad = _pad_servers(m)
    if t_pad != t:
        extra = t_pad - t
        object_ids = jnp.concatenate(
            [object_ids, jnp.zeros((extra, n), object_ids.dtype)])
        lengths = jnp.concatenate(
            [lengths, jnp.zeros((extra, n), lengths.dtype)])
        valid = jnp.concatenate(
            [valid, jnp.zeros((extra, n), valid.dtype)])
        tables = jnp.concatenate(
            [tables, jnp.broadcast_to(init_table(m),
                                      (extra,) + tables.shape[1:])])
        seeds = jnp.concatenate([seeds, jnp.zeros((extra,), seeds.dtype)])
        win_rates = jnp.concatenate(
            [win_rates, jnp.ones((extra,) + win_rates.shape[1:],
                                 win_rates.dtype)])
    pad = ((0, 0), (0, 0), (0, m_pad - m))
    tables_p = jnp.pad(tables.astype(jnp.float32), pad)
    rates_p = jnp.pad(win_rates.astype(jnp.float32), pad)
    choices, lats, ftab, wloads, metrics = sched_stream_call(
        object_ids.astype(jnp.int32), lengths.astype(jnp.float32),
        valid.astype(jnp.int32), tables_p,
        seeds.reshape(t_pad, 1).astype(jnp.uint32), rates_p,
        n_servers=n_servers, window_size=window_size, threshold=threshold,
        lam=lam, alpha=alpha, window_dt=window_dt, policy=policy,
        observe=observe, renorm=renorm, trial_tile=tile, nltr_n=nltr_n,
        probe_choices=probe_choices, ablate=ablate, interpret=interpret)
    return (choices[:t], lats[:t], ftab[:t, :, :m], wloads[:t, :, :m],
            metrics[:t, :N_METRICS])


@functools.partial(jax.jit, static_argnames=("n_servers", "window_size",
                                             "threshold", "lam", "alpha",
                                             "window_dt", "policy",
                                             "observe", "renorm",
                                             "trial_tile", "client_tile",
                                             "nltr_n", "probe_choices",
                                             "merge_mean", "interpret"))
def sched_stream_grid(object_ids: jax.Array, lengths: jax.Array,
                      valid: jax.Array, tables: jax.Array, seeds: jax.Array,
                      win_rates: jax.Array, *, n_servers: int,
                      window_size: int, threshold: float = 0.0,
                      lam: float = 32.0, alpha: float = 0.25,
                      window_dt: float = 0.0, policy: str = "ect",
                      observe: bool = True, renorm: bool = True,
                      trial_tile: Optional[int] = None,
                      client_tile: Optional[int] = None,
                      nltr_n: int = 2, probe_choices: int = 2,
                      merge_mean: bool = True,
                      interpret: Optional[bool] = None
                      ) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array,
                                 jax.Array, jax.Array, jax.Array, jax.Array,
                                 jax.Array]:
    """2-D (trials × clients) grid kernel (DESIGN.md §11): T trials of C
    private-log client streams — the per_client contention model's whole
    Monte-Carlo sweep — as ONE ``pallas_call``.

    object_ids/lengths/valid: (T, C, N) per-client request slices (N =
    W * window_size, padding rows ``valid == False``; a client whose
    slice is ALL padding is a phantom client and is masked out of every
    cross-client aggregate); tables: (T, C, 4, M) private packed log
    tensors; seeds: (T, C) uint32 LCG states; win_rates: (T, W, M)
    per-TRIAL true service rates (a trial's clients share its trace).
    T / C pad up to ``trial_tile`` / ``client_tile`` multiples with
    inert streams and the grid runs ``(ceil(T/tt), ceil(C/ct))``
    program instances — bit-exact per stream vs. mapping
    :func:`sched_stream` over every (trial, client) pair.

    Returns (choices (T, C, N) int32, latencies (T, C, N) f32,
    final_tables (T, C, 4, M) f32, window_loads (T, C, W, M) f32,
    metrics (T, C, N_METRICS) f32 per stream — the kernel's outputs —
    and the cross-client merges of those outputs, folded here with the
    `policy_core` twins the jax backend runs (client-tile association):
    cm_wloads (T, W, M) f32 — the masked client-MEAN post-drain loads
    (`policy_core.masked_client_mean`), or the raw masked client SUM
    when ``merge_mean=False`` (the per-device partial the sharded
    sweep's `policy_core.psum_tree` folds across devices, DESIGN.md
    §12) — cm_metrics (T, N_CMETRICS) f32 cross-client merged rows
    (`policy_core.client_stream_metrics`; its MET_P99 lane is the
    MERGED nearest-rank p99 over the whole trial's latency block when
    ``merge_mean=True``, 0 otherwise — DESIGN.md §14), and cm_lats /
    cm_lval (T, C, N) f32 — the merged latency block: grouped-step
    latencies masked to 0 where invalid, plus 0/1 validity (what the
    sharded sweep all-gathers to bisect the global merged p99)."""
    _check_policy(policy, n_servers, nltr_n)
    interpret = _auto_interpret(interpret)
    t, c, n = object_ids.shape
    m = tables.shape[-1]
    tile_t = resolve_trial_tile(t, trial_tile)
    tile_c = resolve_client_tile(c, client_tile)
    t_pad = -(-t // tile_t) * tile_t
    c_pad = -(-c // tile_c) * tile_c
    m_pad = _pad_servers(m)

    def pad_streams(a, fill):
        """Pad the client then the trial axis with inert streams."""
        if c_pad != c:
            extra = jnp.broadcast_to(fill, (a.shape[0], c_pad - c)
                                     + a.shape[2:]).astype(a.dtype)
            a = jnp.concatenate([a, extra], axis=1)
        if t_pad != t:
            extra = jnp.broadcast_to(fill, (t_pad - t,) + a.shape[1:]
                                     ).astype(a.dtype)
            a = jnp.concatenate([a, extra], axis=0)
        return a

    object_ids = pad_streams(object_ids.astype(jnp.int32), 0)
    lengths = pad_streams(lengths.astype(jnp.float32), 0.0)
    valid = pad_streams(valid.astype(jnp.int32), 0)
    seeds = pad_streams(seeds.astype(jnp.uint32), jnp.uint32(0))
    tables = pad_streams(tables.astype(jnp.float32), init_table(m))
    if t_pad != t:   # inert trials: unit rates (never divided by ~0)
        win_rates = jnp.concatenate(
            [win_rates, jnp.ones((t_pad - t,) + win_rates.shape[1:],
                                 win_rates.dtype)])
    pad = ((0, 0), (0, 0), (0, m_pad - m))
    tables_p = jnp.pad(tables, ((0, 0),) + pad)
    rates_p = jnp.pad(win_rates.astype(jnp.float32), pad)
    choices, lats, ftab, wloads, metrics = sched_stream_grid_call(
        object_ids, lengths, valid, tables_p, seeds, rates_p,
        n_servers=n_servers, window_size=window_size, threshold=threshold,
        lam=lam, alpha=alpha, window_dt=window_dt, policy=policy,
        observe=observe, renorm=renorm, trial_tile=tile_t,
        client_tile=tile_c, nltr_n=nltr_n, probe_choices=probe_choices,
        interpret=interpret)
    choices, lats = choices[:t, :c], lats[:t, :c]
    wloads, metrics = wloads[:t, :c, :, :m], metrics[:t, :c, :N_METRICS]

    # cross-client merges over REAL clients (a client is real iff its
    # slice holds a valid request): the policy_core twins the jax
    # backend runs, with the kernel grid's client-tile association
    with tune_profile.stage("merge"):
        validb = valid[:t, :c] != 0
        cvalid = jnp.any(validb, axis=-1)                        # (T, C)
        cm_lats = jnp.where(validb, lats, 0.0)
        cm_lval = jnp.where(validb, 1.0, 0.0)
        merge_wl = masked_client_mean if merge_mean else masked_client_sum
        cm_wl = jax.vmap(lambda w, v: merge_wl(w, v, tile_c))(wloads, cvalid)
        if merge_mean:
            cm_met = jax.vmap(
                lambda mt, v, ml, mv: client_stream_metrics(
                    mt, v, tile_c, merged_lats=ml, merged_valid=mv)
            )(metrics, cvalid, cm_lats, validb)
        else:
            cm_met = jax.vmap(
                lambda mt, v: client_stream_metrics(mt, v, tile_c)
            )(metrics, cvalid)
    return (choices, lats, ftab[:t, :c, :, :m], wloads, metrics, cm_wl,
            cm_met, cm_lats, cm_lval)
