"""Jitted window/step scheduling engine (paper §3.2).

The time series of queued I/O requests is split into fixed-size *time
windows*; within a window the requests are grouped into *steps* (all
requests on the same object form one step so the object is fetched once,
Fig. 7) and scheduled sequentially against the client-side statistic log.

Everything is shape-static so a full paper evaluation (100 trials x 5
policies x 2000 requests) runs as a handful of jitted programs:

* ``group_by_object``    — step formation (same-object aggregation) with a
                           static output size (padding marked invalid).
* ``run_window``         — plan (sorts / sections) + ``lax.scan`` over the
                           window's steps, applying Eqs. (1)-(3) per step.
* ``run_stream``         — ``lax.scan`` over windows.

Outputs per request: the chosen server (original request order), the
probe-message count (0 for all log-assisted policies, 2/request for the
SC'14 two-choice baseline), and the estimated completion latency.

Temporal model (DESIGN.md §Temporal-model): ``run_stream`` optionally
takes a :class:`ClusterTrace` — a static-shape schedule of per-server
service-rate events (straggler onset/recovery, flapping, correlated rack
degradation, permanent heterogeneity).  Between windows the engine
applies the trace's rates, drains each server's queue for ``window_dt``
virtual seconds (:func:`repro.core.statlog.advance_time`), and records a
per-request estimated completion time; completions feed the log's
``ewma_lat`` so the ECT policy sees *slow* servers in the JAX path.  With
``trace=None`` (or the degenerate all-equal-rates, ``window_dt=0``
trace) the engine reproduces the paper's static-load model exactly.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import policies as P
from repro.core import policy_core, statlog
from repro.core.statlog import LogConfig, SchedState
from repro.tune import profile as tune_profile

# Policies the Pallas backend (kernels/sched_select) implements in-VMEM —
# since the in-VMEM bitonic sort (DESIGN.md §10) this is every engine
# policy: the whole §3.4 library dispatches through the kernel.
KERNEL_POLICIES = ("ect", "trh", "mlml", "nltr", "rr", "two_choice")


class Workload(NamedTuple):
    """A batch of I/O requests (static length; ``valid`` marks padding)."""

    object_ids: jax.Array  # (R,) int32
    lengths: jax.Array     # (R,) float32, MB
    valid: jax.Array       # (R,) bool

    @property
    def n_requests(self) -> int:
        return self.object_ids.shape[0]


class ClusterTrace(NamedTuple):
    """Static-shape schedule of service-rate change events.

    Row ``e`` says: from virtual time ``times[e]`` on, server ``i`` serves
    at ``rates[e, i]`` MB/s.  ``times[0]`` must be 0 (the base rates).
    Piecewise-constant rates express every scenario in the library:
    permanent heterogeneity (1 event), transient stragglers (3), flapping
    (alternating events), correlated rack degradation (rack rows slowed).
    """

    times: jax.Array   # (E,) float32, ascending, times[0] == 0
    rates: jax.Array   # (E, M) float32 MB/s per server

    @property
    def n_events(self) -> int:
        return self.times.shape[0]


def rates_at(trace: ClusterTrace, t: jax.Array) -> jax.Array:
    """(M,) service rates in effect at virtual time ``t``.

    The event's row is picked by a masked sum over the few events with a
    single non-zero term, an exact relocation with no gather op."""
    idx = jnp.clip(jnp.sum(trace.times <= t) - 1, 0, trace.n_events - 1)
    pick = jnp.arange(trace.n_events) == idx
    return jnp.sum(jnp.where(pick[:, None], trace.rates, 0.0), axis=0)


class ScheduleResult(NamedTuple):
    state: SchedState
    chosen: jax.Array        # (R,) int32 server per request (original order)
    probe_msgs: jax.Array    # () int32 total probe messages issued
    redirected: jax.Array    # (R,) bool — True where chosen != default home
    latencies: jax.Array     # (R,) float32 est. completion latency, seconds
    #                          (queue ahead + own bytes, at assignment time)
    window_loads: jax.Array  # (W, M) per-window post-drain load snapshots
    #                          (W=1 for run_window)
    rng: Optional[jax.Array] = None  # final uint32 LCG state (rng="lcg"
    #                          policies; None for the kernel backend which
    #                          keeps its LCG in VMEM)


def _run_totals(x, keys):
    """Sum of each run of equal ``keys`` along the last axis, on the
    run's first row (rows of a run are adjacent).  A segmented suffix
    scan: level ``d`` adds the partial sum ``2**d`` rows on where that
    row is in the same run.  The association is fixed by the run's
    length alone, in row order (its first power-of-two block, plus the
    rest), so every batch shape and context gives the same bits; a run
    of one or two rows is exact (DESIGN.md §18)."""
    w = x.shape[-1]
    shift = 1
    while shift < w:
        same = jnp.concatenate(
            [keys[..., shift:] == keys[..., :-shift],
             jnp.zeros(keys.shape[:-1] + (shift,), bool)], axis=-1)
        nxt = jnp.concatenate(
            [x[..., shift:], jnp.zeros(x.shape[:-1] + (shift,), x.dtype)],
            axis=-1)
        x = jnp.where(same, x + nxt, x)
        shift *= 2
    return x


def group_by_object_with_map(work: Workload) -> Tuple[Workload, jax.Array]:
    """Form steps: aggregate same-object requests into one decision (§3.2).

    Static-shape friendly: output has the same length R, rows in the
    order of a stable sort by object id with invalid rows last; the
    first occurrence of each object carries the summed length,
    duplicates are marked invalid (zero length).  Also returns
    ``req_to_step``: for every ORIGINAL request index, the row of its
    aggregated step (so per-request results can be relocated back with
    `policy_core.permute_from_sorted`).

    Window-local dense form (DESIGN.md §18): one all-pairs integer rank
    (`policy_core.rank_asc`) orders the rows and single-non-zero masked
    sums move them; no gather, scatter or sort.  Fields are ``(..., R)``
    with any leading batch axes, each row of R grouped on its own.
    """
    big = jnp.iinfo(jnp.int32).max
    ids = jnp.where(work.valid, work.object_ids, big)
    rank, req_to_step = policy_core.rank_asc(ids)
    s_ids, s_len = policy_core.permute_to_sorted(
        rank, (ids, work.lengths * work.valid), whole_tile=True)
    is_first = jnp.concatenate(
        [jnp.ones(s_ids.shape[:-1] + (1,), bool),
         s_ids[..., 1:] != s_ids[..., :-1]], axis=-1)
    agg_valid = is_first & (s_ids != big)
    grouped = Workload(
        object_ids=jnp.where(agg_valid, s_ids, 0).astype(jnp.int32),
        lengths=jnp.where(is_first, _run_totals(s_len, s_ids),
                          0.0).astype(jnp.float32),
        valid=agg_valid)
    return grouped, req_to_step


def group_by_object(work: Workload) -> Workload:
    return group_by_object_with_map(work)[0]


def run_window(state: SchedState, work: Workload, key: jax.Array, *,
               policy: P.PolicyConfig, log_cfg: LogConfig,
               group_steps: bool = True,
               observe: bool = False,
               rng0: Optional[jax.Array] = None) -> ScheduleResult:
    """Schedule one time window's requests against the log.

    ``chosen``/``redirected`` come back in ORIGINAL request order (grouped
    same-object steps share one decision).

    ``observe`` (temporal model; on whenever ``run_stream`` has a trace)
    folds each request's estimated effective MB/s into ``ewma_lat`` right
    after its assignment — the completion-feedback path that lets ECT see
    slow servers.  Off by default so the static model (and the Pallas
    kernel's minload semantics) stay bit-exact with the paper.

    ``rng0`` seeds the kernel-compatible LCG stream (``rng="lcg"``
    policies); the final state comes back in ``ScheduleResult.rng`` so
    ``run_stream`` can carry it across windows exactly like the kernel
    carries its VMEM rng across the whole stream."""
    orig_work = work
    req_to_step = None
    if group_steps:
        work, req_to_step = group_by_object_with_map(work)
    r = work.n_requests
    m = state.n_servers
    plan = P.plan_window(policy, state, work.object_ids, work.lengths, work.valid)
    if rng0 is None:
        rng0 = jnp.zeros((), jnp.uint32)

    # Process in plan order; emit (orig_index, chosen) pairs and unpermute.
    # Policies that keep arrival order skip both permutations: they are
    # identities, and the unpermute's scatter of an iota by the same iota
    # aborts the TPU compiler's scatter fusion.
    reorders = policy.name in P.REORDERING_POLICIES
    obj, lens, val = work.object_ids, work.lengths, work.valid
    if reorders:
        obj, lens, val = obj[plan.order], lens[plan.order], val[plan.order]
    keys = jax.random.split(key, r)

    def body(carry, xs):
        st, rng = carry
        pos, o, ln, v, k = xs
        default = (o % m).astype(jnp.int32)
        # NOTE: the LCG advances on padding rows too — the kernel's
        # unconditional draw stream, required for bit-exact parity.
        target, rng = P.select_target_rng(policy, plan, st, pos, o, ln, k,
                                          rng)
        chosen = P.apply_threshold(policy, st, default, target, ln)
        st2 = statlog.apply_assignment(st, chosen, ln, log_cfg)
        # Estimated completion latency: everything queued ahead of (and
        # including) this request, at the server's current service rate.
        lat = statlog.estimated_latency(st2, chosen)
        if observe:
            # Completion feedback: the effective MB/s this request will
            # see folds into ewma_lat — the ECT policy's rate signal (the
            # host twin observes the same via WriteResult.mb_per_s).
            st2 = statlog.observe_completion(
                st2, chosen, ln / jnp.maximum(lat, 1e-9), log_cfg)
        # padding rows leave the log untouched
        st = jax.tree.map(lambda a, b: jnp.where(v, b, a), st, st2)
        return (st, rng), (chosen, chosen != default, jnp.where(v, lat, 0.0))

    pos = jnp.arange(r, dtype=jnp.int32)
    (state, rng), (chosen_sorted, redir_sorted, lat_sorted) = jax.lax.scan(
        body, (state, rng0), (pos, obj, lens, val, keys))
    if log_cfg.renorm:
        state = statlog.renormalize(state)

    # back to request order: plan order -> step order -> original order.
    # The plan's unpermute keeps XLA's gather/scatter; the kernel's §13
    # inverse permutation apply (permute_from_sorted) computes the SAME
    # relocation (property-pinned in tests/test_policies.py).  The step
    # -> request relocation is the kernel path's own (§18).
    if reorders:
        inv = jnp.zeros((r,), jnp.int32).at[plan.order].set(pos)
        chosen_sorted = chosen_sorted[inv]
        redir_sorted = redir_sorted[inv]
        lat_sorted = lat_sorted[inv]
    chosen = chosen_sorted
    redirected = redir_sorted & work.valid
    latencies = lat_sorted * work.valid
    if req_to_step is not None:
        chosen, redirected, latencies = policy_core.permute_from_sorted(
            req_to_step, (chosen, redirected.astype(jnp.int32), latencies),
            whole_tile=True)
        redirected = (redirected != 0) & orig_work.valid
        latencies = latencies * orig_work.valid
    probes = (jnp.sum(work.valid) * policy.probes_per_request).astype(jnp.int32)
    return ScheduleResult(state=state, chosen=chosen, probe_msgs=probes,
                          redirected=redirected, latencies=latencies,
                          window_loads=state.loads[None], rng=rng)


def _window_split(work: Workload, window_size: int):
    """Pad the stream to a multiple of ``window_size`` and reshape to
    (W, window_size) arrays (padding rows invalid)."""
    r = work.n_requests
    n_win = -(-r // window_size)
    pad = n_win * window_size - r

    def pad_to(a, fill=0):
        return jnp.concatenate([a, jnp.full((pad,), fill, a.dtype)]) if pad else a

    obj = pad_to(work.object_ids).reshape(n_win, window_size)
    lens = pad_to(work.lengths).reshape(n_win, window_size)
    val = pad_to(work.valid, False).reshape(n_win, window_size)
    return n_win, obj, lens, val


def _window_rates(state: SchedState, trace: Optional[ClusterTrace],
                  n_win: int, window_dt: float) -> jax.Array:
    """(W, M) service rates in effect at each window open."""
    if trace is not None:
        t_open = jnp.arange(n_win, dtype=jnp.float32) * window_dt
        return jax.vmap(lambda t: rates_at(trace, t))(t_open)
    # static model: keep whatever rates the state carries
    return jnp.broadcast_to(state.rates, (n_win, state.n_servers))


def grouped_latency_block(works: Workload, latencies: jax.Array,
                          window_size: int, group_steps: bool = True
                          ) -> Tuple[jax.Array, jax.Array]:
    """Recover the kernel's MERGED LATENCY BLOCK on the jax backend
    (DESIGN.md §14): grouped-step latencies + validity per stream.

    The kernel path schedules pre-grouped streams, so its in-VMEM block
    (``ClientMerge.lats``/``lats_valid``) holds GROUPED-STEP latencies;
    `run_stream` instead relocates step latencies back to original
    request order (duplicate same-object requests share their step's
    bits).  This helper replays the identical window split + grouping
    and recovers each step's latency with a ``segment_min`` over its
    requests — pure selection over identical f32 values, so the result
    is bit-exact with the kernel block's multiset and
    `policy_core.nearest_rank_p99` over either is bit-identical.

    ``works`` fields and ``latencies`` share a shape ``(..., R)`` with
    any number of leading batch axes; returns ``(lats, valid)`` shaped
    ``(..., N)`` where ``N = ceil(R / window_size) * window_size``
    (invalid steps masked to 0.0; ``valid`` is bool).
    """

    def one(obj_r, len_r, val_r, lat_r):
        n_win, obj, lens, val = _window_split(
            Workload(object_ids=obj_r, lengths=len_r, valid=val_r),
            window_size)
        pad = n_win * window_size - obj_r.shape[0]
        lat_p = (jnp.concatenate([lat_r, jnp.zeros((pad,), lat_r.dtype)])
                 if pad else lat_r)
        lat_w = lat_p.reshape(n_win, window_size)
        if not group_steps:
            return (jnp.where(val, lat_w, 0.0).reshape(-1),
                    val.reshape(-1))
        grouped, req_to_step = group_by_object_with_map(
            Workload(object_ids=obj, lengths=lens, valid=val))
        g_lat = jax.vmap(lambda lr, mp, v: jax.ops.segment_min(
            jnp.where(v, lr, jnp.float32(jnp.inf)), mp,
            num_segments=window_size))(lat_w, req_to_step, val)
        g_lat = jnp.where(grouped.valid, g_lat, 0.0)
        return g_lat.reshape(-1), grouped.valid.reshape(-1)

    fn = one
    for _ in range(latencies.ndim - 1):
        fn = jax.vmap(fn)
    return fn(works.object_ids, works.lengths, works.valid, latencies)


def run_stream(state: SchedState, work: Workload, key: jax.Array, *,
               policy: P.PolicyConfig, log_cfg: LogConfig, window_size: int,
               group_steps: bool = True,
               trace: Optional[ClusterTrace] = None,
               window_dt: float = 0.0,
               observe: Optional[bool] = None,
               backend: str = "jax") -> ScheduleResult:
    """Split the request time series into windows and schedule each (§3.2).

    Pads the stream to a multiple of ``window_size``; padding is invalid.

    Temporal model: window ``w`` opens at virtual time ``w * window_dt``.
    When a ``trace`` is given, the rates in effect at each window start are
    looked up from it before scheduling, and after the window the queues
    drain for ``window_dt`` seconds at those rates.  ``window_dt`` must be
    a static python float (0.0 disables draining — the static model).

    ``observe`` controls the completion-feedback path (see
    :func:`run_window`); default: on exactly when a trace is given.  Pass
    ``observe=False`` with a trace to keep ewma-reading policies (ECT)
    bit-identical to the no-trace path — the degenerate static scenario
    does this (the feedback would differ from the never-observing static
    model even with all-equal rates).

    ``backend`` selects the execution substrate: ``"jax"`` (the lax.scan
    engine, every policy) or ``"kernel"`` (the Pallas temporal kernel —
    the whole stream as ONE ``pallas_call`` with the packed log tensor in
    VMEM; every policy in ``KERNEL_POLICIES``, i.e. the full §3.4
    library since the in-VMEM sorts of DESIGN.md §10).  The two backends
    are bit-exact for the deterministic policies (``ect``, ``mlml``,
    ``rr``); for the randomized ones (``trh``, ``nltr``, ``two_choice``)
    pass ``PolicyConfig(rng="lcg")`` so the jax path replays the
    kernel's LCG stream.
    """
    P.validate_policy(policy, state.n_servers)
    if observe is None:
        observe = trace is not None
    if backend == "kernel":
        return _run_stream_kernel(state, work, key, policy=policy,
                                  log_cfg=log_cfg, window_size=window_size,
                                  group_steps=group_steps, trace=trace,
                                  window_dt=window_dt, observe=observe)
    if backend != "jax":
        raise ValueError(f"backend must be 'jax' or 'kernel', got {backend!r}")
    r = work.n_requests
    n_win, obj, lens, val = _window_split(work, window_size)
    keys = jax.random.split(key, n_win)
    win_rates = _window_rates(state, trace, n_win, window_dt)
    # Drain decrements materialize OUTSIDE the scan body (scan xs) so the
    # in-body drain is a bare subtract — no FMA-contractable product, the
    # §9 bit-exactness contract shared with the kernel backend.
    win_dec = policy_core.window_decrements(win_rates, window_dt)
    # Kernel-compatible LCG seed: both backends derive it identically
    # from the stream key, then carry ONE rng across all windows.
    rng0 = jax.random.bits(key, dtype=jnp.uint32)

    def body(carry, xs):
        st, rng = carry
        o, ln, v, k, rates, dec = xs
        st = st._replace(rates=rates)
        res = run_window(st, Workload(o, ln, v), k, policy=policy,
                         log_cfg=log_cfg, group_steps=group_steps,
                         observe=observe, rng0=rng)
        st = res.state
        if window_dt:
            st = statlog.advance_time(st, jnp.float32(window_dt), dec=dec)
        return (st, res.rng), (res.chosen, res.probe_msgs, res.redirected,
                               res.latencies, st.loads)

    (state, rng), (chosen, probes, redirected, latencies, window_loads) = \
        jax.lax.scan(body, (state, rng0),
                     (obj, lens, val, keys, win_rates, win_dec))
    return ScheduleResult(
        state=state,
        chosen=chosen.reshape(-1)[:r],
        probe_msgs=jnp.sum(probes).astype(jnp.int32),
        redirected=redirected.reshape(-1)[:r],
        latencies=latencies.reshape(-1)[:r],
        window_loads=window_loads,
        rng=rng,
    )


def _run_stream_kernel(state: SchedState, work: Workload, key: jax.Array, *,
                       policy: P.PolicyConfig, log_cfg: LogConfig,
                       window_size: int, group_steps: bool,
                       trace: Optional[ClusterTrace], window_dt: float,
                       observe: bool) -> ScheduleResult:
    """Pallas-backend stream dispatch: grouping / window planning stays on
    the JAX side (same `group_by_object_with_map` as the jax backend, so
    both backends see identical per-window inputs); the per-request
    decision loop — selection, threshold guard, Eq. (1)-(3), completion
    feedback, per-window renorm + drain — runs as one `pallas_call` with
    the packed (4, M) log tensor pinned in VMEM."""
    from repro.kernels.sched_select import ops as kops

    if policy.name not in KERNEL_POLICIES:
        raise ValueError(
            f"backend='kernel' supports {KERNEL_POLICIES}, got "
            f"{policy.name!r}")
    r = work.n_requests
    m = state.n_servers
    n_win, obj, lens, val = _window_split(work, window_size)
    if group_steps:
        grouped, req_to_step = group_by_object_with_map(
            Workload(obj, lens, val))
        g_obj, g_lens, g_val = (grouped.object_ids, grouped.lengths,
                                grouped.valid)
    else:
        g_obj, g_lens, g_val, req_to_step = obj, lens, val, None
    win_rates = _window_rates(state, trace, n_win, window_dt)
    seed = jax.random.bits(key, dtype=jnp.uint32)

    choices, lats, table, wloads = kops.sched_stream(
        g_obj.reshape(-1), g_lens.reshape(-1), g_val.reshape(-1),
        state.log, seed, win_rates,
        n_servers=m, window_size=window_size, threshold=policy.threshold,
        lam=log_cfg.lam, alpha=log_cfg.ewma_alpha, window_dt=window_dt,
        policy=policy.name, observe=observe, renorm=log_cfg.renorm,
        nltr_n=policy.nltr_n, probe_choices=policy.probe_choices)

    return _kernel_bookkeeping(state, choices, lats, table, wloads, g_obj,
                               g_val, val, req_to_step, win_rates[-1],
                               policy=policy, window_dt=window_dt,
                               n_win=n_win, window_size=window_size, r=r)


def _kernel_bookkeeping(state: SchedState, choices, lats, table, wloads,
                        g_obj, g_val, val, req_to_step, rates_last, *,
                        policy: P.PolicyConfig, window_dt: float, n_win: int,
                        window_size: int, r: int) -> ScheduleResult:
    """Host-side bookkeeping the kernel leaves behind, for ONE stream:
    redirect derivation, grouped-step -> request relocation, per-server
    assignment counts, probe accounting (from
    ``PolicyConfig.probes_per_request`` — nonzero only for two_choice)
    and the vclock/free_at replay.  Shared by the sequential kernel path
    and (vmapped) `run_stream_batch`, so batch-vs-sequential parity is
    structural rather than maintained in two copies.

    choices/lats: (N,) over grouped steps; g_obj/g_val/val (and
    req_to_step when grouping): (n_win, window_size); table: (4, M);
    wloads: (n_win, M); rates_last: (M,) rates at the last window.
    """
    m = table.shape[-1]
    chosen_w = choices.reshape(n_win, window_size)
    lat_w = lats.reshape(n_win, window_size)
    redir_w = (chosen_w != (g_obj % m).astype(jnp.int32)) & g_val
    if req_to_step is not None:
        chosen_w, lat_w, redir_w = policy_core.permute_from_sorted(
            req_to_step, (chosen_w, lat_w, redir_w.astype(jnp.int32)),
            whole_tile=True)
        redir_w = redir_w != 0
    lat_w = lat_w * val
    redir_w = redir_w & val

    counts = jax.ops.segment_sum(g_val.reshape(-1).astype(jnp.int32),
                                 choices, num_segments=m)
    if window_dt:
        vclock = state.vclock
        for _ in range(n_win):   # sequential f32 adds: match advance_time
            vclock = vclock + jnp.float32(window_dt)
        free_at = vclock + table[policy_core.ROW_LOADS] / jnp.maximum(
            rates_last, 1e-6)
    else:
        vclock, free_at = state.vclock, state.free_at
    fstate = SchedState(log=table, n_assigned=state.n_assigned + counts,
                        rates=rates_last, vclock=vclock, free_at=free_at)
    probes = (jnp.sum(g_val) * policy.probes_per_request).astype(jnp.int32)
    return ScheduleResult(
        state=fstate,
        chosen=chosen_w.reshape(-1)[:r],
        probe_msgs=probes,
        redirected=redir_w.reshape(-1)[:r],
        latencies=lat_w.reshape(-1)[:r],
        window_loads=wloads,
    )


@functools.partial(jax.jit, static_argnames=("policy", "log_cfg",
                                             "window_size", "group_steps",
                                             "window_dt", "observe",
                                             "backend"))
def run_stream_jit(state, work, key, *, policy, log_cfg, window_size,
                   group_steps=True, trace=None, window_dt=0.0,
                   observe=None, backend="jax"):
    return run_stream(state, work, key, policy=policy, log_cfg=log_cfg,
                      window_size=window_size, group_steps=group_steps,
                      trace=trace, window_dt=window_dt, observe=observe,
                      backend=backend)


class ClientMerge(NamedTuple):
    """Per-trial cross-client aggregates of the 2-D (trials × clients)
    grid kernel's streams (DESIGN.md §11, merged after the kernel by
    `ops.sched_stream_grid`, §17) — the per_client
    contention model's "typical client" view, merged over REAL clients
    (a client is real iff its slice scheduled at least one valid
    request; phantom padded clients are masked out with the
    `policy_core.masked_client_sum` association).

    ``lats``/``lats_valid`` are the MERGED LATENCY BLOCK (DESIGN.md
    §14): every client's grouped-step latencies (masked to 0 where
    invalid) and 0/1 validity.  With ``merge_mean=True`` the merge has
    already bisected the trial's cross-client nearest-rank p99 out of it into
    ``metrics[:, MET_P99]``; with ``merge_mean=False`` (the sharded
    sweep) the lane is 0 and the raw block ships so
    `parallel.sweep.run_sweep` can all-gather it and bisect the GLOBAL
    p99 once — `policy_core.nearest_rank_p99` is order- and
    layout-insensitive, so the gather order cannot drift it."""

    window_loads_mean: jax.Array  # (T, W, M) masked client-mean snapshots
    metrics: jax.Array            # (T, N_CMETRICS) merged MET_* rows
    lats: jax.Array               # (T, C, N) masked grouped-step latencies
    lats_valid: jax.Array         # (T, C, N) 0/1 f32 validity


def run_stream_batch(states: SchedState, works: Workload, keys: jax.Array, *,
                     policy: P.PolicyConfig, log_cfg: LogConfig,
                     window_size: int, group_steps: bool = True,
                     traces: Optional[ClusterTrace] = None,
                     window_dt: float = 0.0,
                     observe: Optional[bool] = None,
                     trial_tile: Optional[int] = None,
                     client_tile: Optional[int] = None,
                     merge_mean: bool = True,
                     ablate: int = 0,
                     backend: str = "kernel"
                     ) -> Tuple[ScheduleResult, Optional[jax.Array],
                                Optional[ClientMerge]]:
    """Batched dispatch: a whole batch of `run_stream` traces as ONE
    pallas_call, for an arbitrary leading batch shape.

    ``states`` / ``works`` / ``keys`` carry either a ``(T,)`` leading
    trial axis (the PR-3 trial grid) or a ``(T, C)`` (trials × clients)
    axis pair — the per_client contention model, where each of a
    trial's C clients schedules its private request slice against its
    own log and ``traces`` stays per-TRIAL (a trial's clients share the
    cluster's rate schedule).  The JAX-side prep is the per-stream
    `run_stream` prep vmapped (window split, `group_by_object_with_map`
    step formation, per-window trace rates), so every stream sees
    bit-identical inputs to the sequential path; the scheduling itself
    runs on the trial-grid kernel (``grid = ceil(T / trial_tile)``) or
    the 2-D grid kernel (``grid = (ceil(T / tt), ceil(C / ct))``,
    DESIGN.md §11), streams vectorized over VMEM sublanes either way.

    Returns ``(result, metrics, client_merge)``: ``result`` is a
    ScheduleResult whose fields all carry the leading batch axes,
    bit-exact per stream vs. `run_stream(backend="kernel")` under
    ``lax.map``; ``metrics`` is the kernel's fused in-VMEM reduction,
    ``(T[, C], N_METRICS)`` f32 in `policy_core.MET_*` order (makespan /
    nearest-rank p99 / latency sum / latency max / valid count over the
    scheduled steps) — the headline sweep numbers without an HBM
    round-trip of the latency blocks; ``client_merge`` is the
    :class:`ClientMerge` cross-client row for the (T, C) form and
    ``None`` for the (T,) form.

    ``backend="jax"`` runs the same batch on the vmapped lax.scan
    engine instead (the dispatch `simulate._run_batched` used inline
    before the sharded sweep unified both backends behind this one
    entry point): bit-exact per stream vs. the kernel path, returning
    ``(result, None, None)`` — no fused metrics/merge rows; callers
    compute the `policy_core` merge twins host-side.  ``merge_mean``
    (kernel (T, C) form only): ``False`` ships `ClientMerge.
    window_loads_mean` as the raw masked client SUM instead of the mean
    — the pre-reduced per-device block that the sharded sweep
    (`parallel/sweep.py`, DESIGN.md §12) folds across devices with
    `policy_core.psum_tree` before dividing once, globally.

    ``ablate`` (kernel (T,) form only) drops trailing kernel window
    phases for differential per-phase profiling (DESIGN.md §16, see
    `repro.tune.profile.kernel_phase_profile`); outputs past the
    dropped phase are zeros, so nonzero levels are timing-only.

    Stages (`repro.tune.profile.stage`, name scopes read from the
    device trace, DESIGN.md §16): ``engine_prep`` covers the split,
    grouping, seeds and window rates, ``kernel`` the kernel wrapper
    (whose (T, C) form scopes its cross-client merge ``merge``), and
    ``book`` the bookkeeping.  They name the ops; they time nothing.
    """
    from repro.kernels.sched_select import ops as kops

    if backend not in ("jax", "kernel"):
        raise ValueError(f"backend={backend!r} must be 'jax' or 'kernel'")
    if ablate and backend != "kernel":
        raise ValueError("ablate profiling levels need backend='kernel'")
    P.validate_policy(policy, states.n_servers)
    if observe is None:
        observe = traces is not None

    if backend == "jax":
        run1 = functools.partial(
            run_stream, policy=policy, log_cfg=log_cfg,
            window_size=window_size, group_steps=group_steps,
            window_dt=window_dt, observe=observe, backend="jax")
        fn = lambda st, w, k, tr: run1(st, w, k, trace=tr)  # noqa: E731
        tr_ax = None if traces is None else 0
        if works.object_ids.ndim == 3:   # (T, C): traces stay per-trial
            inner = jax.vmap(fn, in_axes=(0, 0, 0, None))
            res = jax.vmap(inner, in_axes=(0, 0, 0, tr_ax))(
                states, works, keys, traces)
        else:
            res = jax.vmap(fn, in_axes=(0, 0, 0, tr_ax))(
                states, works, keys, traces)
        return res, None, None

    if policy.name not in KERNEL_POLICIES:
        raise ValueError(
            f"run_stream_batch supports {KERNEL_POLICIES}, got "
            f"{policy.name!r}")
    batch_shape = works.object_ids.shape[:-1]     # (T,) or (T, C)
    two_d = len(batch_shape) == 2
    if ablate and two_d:
        raise ValueError("ablate profiling levels support the trial-grid "
                         "(1-D) form only")
    r = works.object_ids.shape[-1]
    m = states.n_servers

    n_win = -(-r // window_size)

    def prep(state, work, key):
        _, obj, lens, val = _window_split(work, window_size)
        if group_steps:
            grouped, req_to_step = group_by_object_with_map(
                Workload(obj, lens, val))
            g_obj, g_lens, g_val = (grouped.object_ids, grouped.lengths,
                                    grouped.valid)
        else:
            g_obj, g_lens, g_val, req_to_step = obj, lens, val, None
        seed = jax.random.bits(key, dtype=jnp.uint32)
        return (g_obj.reshape(-1), g_lens.reshape(-1), g_val.reshape(-1),
                seed, val, req_to_step)

    vprep = jax.vmap(jax.vmap(prep)) if two_d else jax.vmap(prep)
    with tune_profile.stage("engine_prep"):
        g_obj, g_lens, g_val, seeds, val, req_to_step = \
            vprep(states, works, keys)
        if traces is not None:
            win_rates = jax.vmap(
                lambda tr: _window_rates(None, tr, n_win, window_dt)
            )(traces)
        else:
            # 2-D: rates are per TRIAL (client-shared) — read client 0's
            # row
            rate_states = (jax.tree.map(lambda a: a[:, 0], states)
                           if two_d else states)
            win_rates = jax.vmap(
                lambda st: _window_rates(st, None, n_win, window_dt)
            )(rate_states)

    kw = dict(n_servers=m, window_size=window_size,
              threshold=policy.threshold, lam=log_cfg.lam,
              alpha=log_cfg.ewma_alpha, window_dt=window_dt,
              policy=policy.name, observe=observe, renorm=log_cfg.renorm,
              nltr_n=policy.nltr_n, probe_choices=policy.probe_choices)
    # ``kernel`` scopes the jitted wrapper's call, not the pallas_call
    # inside it: the custom call takes its HLO name from the innermost
    # scope, and keeps the wrapper's (``sched_stream_batch.1``).  The
    # (T, C) wrapper scopes its cross-client merge ``merge`` inside.
    with tune_profile.stage("kernel"):
        if two_d:
            (choices, lats, tables, wloads, metrics,
             cm_wl, cm_met, cm_lats, cm_lval) = kops.sched_stream_grid(
                g_obj, g_lens, g_val, states.log, seeds, win_rates,
                trial_tile=trial_tile, client_tile=client_tile,
                merge_mean=merge_mean, **kw)
            merged = ClientMerge(window_loads_mean=cm_wl, metrics=cm_met,
                                 lats=cm_lats, lats_valid=cm_lval)
        else:
            choices, lats, tables, wloads, metrics = kops.sched_stream_batch(
                g_obj, g_lens, g_val, states.log, seeds, win_rates,
                trial_tile=trial_tile, ablate=ablate, **kw)
            merged = None

    # host-side bookkeeping: the SAME single-stream helper as the
    # sequential kernel path, vmapped over the batch axes (every op in
    # it is exact — one-hot relocations, bool masks, integer segment sums,
    # elementwise f32 adds — so batching cannot drift it).
    book = functools.partial(
        _kernel_bookkeeping, policy=policy, window_dt=window_dt,
        n_win=n_win, window_size=window_size, r=r)
    if two_d:
        # rates_last is per trial: broadcast over the client axis
        vbook = jax.vmap(jax.vmap(book, in_axes=(0,) * 9 + (None,)))
    else:
        vbook = jax.vmap(book)
    with tune_profile.stage("book"):
        result = vbook(
            states, choices, lats, tables, wloads,
            g_obj.reshape(batch_shape + (n_win, window_size)),
            g_val.reshape(batch_shape + (n_win, window_size)), val,
            req_to_step, win_rates[:, -1])
    return result, metrics, merged
