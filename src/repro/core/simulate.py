"""Paper §4 simulation harness.

Reproduces the evaluation environment of the paper:

* 100 object storage servers, 200 compute nodes;
* 2,000 I/O requests per trial in three size classes — small (< 4 MB),
  medium (4-10 MB), large (> 10 MB, up to ~1 GB so the large-only workload
  spans O(20 GB)-O(2 TB) as in §4);
* initial OSS loads ~ Normal(mean, small sigma);
* 100 trials, reporting the average per-OSS load;
* straggler injection: 10 % of servers receive 5x the average load.

Everything dispatches through ONE batched trial runner (`_run_batched`)
per policy, jitted end to end.

Two client models are provided:

* ``shared_log``  (default, used for the paper's figures): all requests go
  through one collective statistic log — the paper's §3.2 collective-I/O
  scheduling model.
* ``per_client``  (contention study, beyond the paper's figures): requests
  are partitioned over ``n_clients`` independent logs which do NOT see each
  other's decisions; reported loads are the true per-server sums.  This
  quantifies the multi-client blind spot discussed in DESIGN.md.

Both models run on either backend: ``backend="jax"`` (vmapped lax.scan
engine) or ``backend="kernel"`` — the whole sweep as ONE pallas_call,
grid = trial tiles for shared_log (DESIGN.md §9) and trial tiles ×
client tiles for per_client (DESIGN.md §11), bit-exact across backends.
"""

from __future__ import annotations

import dataclasses
import functools
import warnings
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import engine, policies, policy_core, statlog
from repro.core.engine import ClusterTrace, Workload
from repro.core.policies import PolicyConfig
from repro.core.statlog import LogConfig, SchedState
from repro.tune import profile as tune_profile
from repro.tune import table as tune_table

SIZE_CLASSES = ("small", "medium", "large", "mixed")

SCENARIOS = ("static", "permanent_slow", "transient", "flapping",
             "correlated_rack")

# Canonical policy set for temporal sweeps (the paper's log-assisted
# policies + the rate-aware ECT extension); benchmarks import this so the
# ranking tables track the scenario/policy libraries automatically.
SWEEP_POLICIES = ("rr", "mlml", "trh", "nltr", "ect")


@dataclasses.dataclass(frozen=True)
class ScenarioConfig:
    """Temporal straggler scenario (DESIGN.md §Temporal-model).

    Generates a :class:`~repro.core.engine.ClusterTrace` per trial (random
    straggler identities, deterministic per trial key):

    * ``static``          — all rates equal, no events; with the default
                            ``window_dt = 0`` this is the degenerate trace
                            that reproduces the paper's static-load model
                            bit-for-bit (Fig. 18's extra-load stragglers
                            stay available via ``SimConfig.straggler_frac``).
    * ``permanent_slow``  — a random ``straggler_frac`` subset serves at
                            ``base/slow_factor`` for the whole run
                            (permanent heterogeneity, arXiv:1911.05918's
                            slow-service setting).
    * ``transient``       — same subset degrades at ``onset`` and recovers
                            at ``recover`` (fractions of the stream
                            horizon) — the IOPathTune-style runtime drift.
    * ``flapping``        — the subset alternates slow/normal ``n_flaps``
                            times over the horizon.
    * ``correlated_rack`` — a random contiguous rack of ``rack_size``
                            servers degrades at ``onset`` and stays slow
                            (correlated failure domain).
    """

    name: str = "static"
    base_rate_mb_s: float = 200.0
    slow_factor: float = 8.0
    straggler_frac: float = 0.10
    # None -> auto: the time in which the *healthy* cluster exactly drains
    # one window's bytes, so stragglers accumulate queue (static -> 0.0).
    window_dt: Optional[float] = None
    onset: float = 0.25       # fraction of horizon (transient/flapping/rack)
    recover: float = 0.65     # transient recovery point (fraction of horizon)
    n_flaps: int = 8
    rack_size: int = 8

    def __post_init__(self):
        if self.name not in SCENARIOS:
            raise ValueError(
                f"unknown scenario {self.name!r}; choose from {SCENARIOS}")


@dataclasses.dataclass(frozen=True)
class SimConfig:
    """Paper §4 simulation parameters (defaults = the paper's numbers)."""

    n_servers: int = 100
    n_clients: int = 200
    n_requests: int = 2000
    n_trials: int = 100
    workload: str = "mixed"          # small | medium | large | mixed
    window_size: int = 100           # requests per time window
    init_load_mean: float = 50.0     # MB, Normal initial loads
    init_load_std: float = 5.0       # "small standard deviation"
    straggler_frac: float = 0.0      # 0.10 for the Fig. 18 experiment
    straggler_factor: float = 5.0    # 5x average extra load on stragglers
    client_model: str = "shared_log"  # shared_log | per_client
    # temporal scenario (None = the seed's static-load model, no trace)
    scenario: Optional[ScenarioConfig] = None
    # scheduling substrate: "jax" (lax.scan engine, every policy) or
    # "kernel" (the Pallas trial-grid kernel — every §3.4 policy incl.
    # the sort-based mlml/nltr (DESIGN.md §10); ALL trials run as ONE
    # pallas_call, grid = trial tiles for shared_log (DESIGN.md §9) and
    # trial tiles x client tiles for per_client (DESIGN.md §11)).
    backend: str = "jax"
    # trials per kernel program instance (kernel backend; None = the
    # kernels package default, the native f32 sublane count 8)
    trial_tile: Optional[int] = None
    # clients per 2-D-grid program instance (per_client model; None =
    # `policy_core.DEFAULT_CLIENT_TILE`).  Also the block width of the
    # cross-client merge association (`policy_core.masked_client_sum`),
    # so it is resolved identically on the jax backend.
    client_tile: Optional[int] = None
    # tile resolution mode (DESIGN.md §16): "default" = the static
    # resolver defaults (the pre-tuner behaviour); "fused" = the
    # `policy_core.resolve_grid_tiles` multi-trial client block (deepen
    # the trial tile when the client tile is small); "tuned" = the
    # cached `repro.tune` autotuner winner for this configuration (a
    # cache miss degrades to "fused").  Whatever the mode, the pair is
    # resolved ONCE per dispatch (`repro.tune.table.resolve_sim_tiles`)
    # and threaded through every layer, so the tiles stay association
    # parameters; explicit trial_tile/client_tile settings always win.
    tiles: str = "default"
    # trial prep/post halo dispatch (DESIGN.md §14): "batched" traces
    # `_trial_setup` / `_trial_result` ONCE for the whole trial batch
    # (vmap) — bit-identical to the sequential shapes because the
    # shape-sensitive reductions inside go through pinned association
    # primitives (`policy_core.absorb_probs` / `server_segment_sum`);
    # "sequential" is the lax.map escape hatch, the per-trial-shape
    # parity oracle the batched path is asserted against.
    prep: str = "batched"
    # device mesh of the sharded sweep dispatch (parallel/sweep.py,
    # DESIGN.md §12): None = single-device; ``(t_dev,)`` shards the
    # trial axis over t_dev devices; ``(t_dev, c_dev)`` also shards the
    # per_client client axis, lifting the cross-client merges to
    # psum_tree/pmax collectives.  The shape's product must divide
    # `jax.device_count()` (checked at dispatch by
    # `launch.mesh.make_sweep_mesh`, which names the device count).
    mesh_shape: Optional[Tuple[int, ...]] = None
    # size-class boundaries (MB) per §4
    small_lo: float = 0.25
    small_hi: float = 4.0
    medium_hi: float = 10.0
    large_hi: float = 1024.0

    def __post_init__(self):
        # real exceptions, not asserts: `python -O` strips asserts, and a
        # mis-built sweep config must fail loudly either way.
        if self.workload not in SIZE_CLASSES:
            raise ValueError(
                f"workload={self.workload!r} is not one of {SIZE_CLASSES}")
        if self.client_model not in ("shared_log", "per_client"):
            raise ValueError(
                f"client_model={self.client_model!r} must be 'shared_log' "
                "or 'per_client'")
        if self.backend not in ("jax", "kernel"):
            raise ValueError(
                f"backend={self.backend!r} must be 'jax' or 'kernel'")
        if self.prep not in ("batched", "sequential"):
            raise ValueError(
                f"prep={self.prep!r} must be 'batched' (vmapped trial "
                "prep/post, DESIGN.md §14) or 'sequential' (the lax.map "
                "parity oracle)")
        if self.n_clients < 1:
            raise ValueError(
                f"n_clients={self.n_clients!r} must be >= 1 (the "
                "per_client contention model partitions n_requests="
                f"{self.n_requests} over the clients)")
        if self.trial_tile is not None and self.trial_tile < 1:
            raise ValueError(
                f"trial_tile={self.trial_tile!r} must be a positive trial"
                " count per kernel program instance (or None for the"
                " kernels-package default)")
        if self.client_tile is not None and self.client_tile < 1:
            raise ValueError(
                f"client_tile={self.client_tile!r} must be a positive"
                " client count per 2-D-grid program instance (or None for"
                f" the policy_core default; n_clients={self.n_clients})")
        if self.tiles not in tune_table.TILE_MODES:
            raise ValueError(
                f"tiles={self.tiles!r} must be one of "
                f"{tune_table.TILE_MODES} (DESIGN.md §16)")
        if self.mesh_shape is not None:
            try:
                ms = tuple(int(s) for s in self.mesh_shape)
            except (TypeError, ValueError):
                raise ValueError(
                    f"mesh_shape={self.mesh_shape!r} must be a tuple of "
                    "1 or 2 positive device counts ((trials,) or "
                    "(trials, clients)), or None for the single-device "
                    "dispatch") from None
            # normalize (lists hash differently; jit statics need a tuple)
            object.__setattr__(self, "mesh_shape", ms)
            if len(ms) not in (1, 2) or any(s < 1 for s in ms):
                raise ValueError(
                    f"mesh_shape={ms!r} must be (trials,) or "
                    "(trials, clients) positive device counts (or None "
                    "for the single-device dispatch)")
            if len(ms) == 2 and ms[1] > 1 \
                    and self.client_model != "per_client":
                raise ValueError(
                    f"mesh_shape={ms} shards a client axis but "
                    f"client_model={self.client_model!r} has none — use "
                    "client_model='per_client' or a (trials,) mesh")

    @property
    def n_windows(self) -> int:
        return -(-self.n_requests // self.window_size)


class TrialResult(NamedTuple):
    """Per-trial outputs (leading trial axis after vmap)."""

    server_loads: jax.Array    # (M,) final true load per server, MB
    n_assigned: jax.Array      # (M,) requests landed per server
    chosen: jax.Array          # (R,) server per request
    probe_msgs: jax.Array      # () probe messages issued
    straggler_hits: jax.Array  # () requests landed on injected stragglers
    redirected: jax.Array      # () requests redirected away from default
    init_loads: jax.Array      # (M,) initial (pre-scheduling) loads
    straggler_mask: jax.Array  # (M,) bool — load-injected OR trace-slowed
    # -- temporal extension (meaningful when cfg.scenario is set) ----------
    latencies: jax.Array       # (R,) est. completion latency per request, s
    phase_time: jax.Array      # () makespan: latest est. completion time, s
    window_loads: jax.Array    # (W, M) post-drain load snapshot per window
    #   (per_client: masked client-MEAN of the real clients' private
    #   views — the typical client, phantom padded clients excluded)
    window_size_eff: jax.Array  # () int32 EFFECTIVE per-stream window size:
    #   cfg.window_size, except under per_client where it clamps to the
    #   per-client slice length min(window_size, ceil(R / n_clients)) —
    #   recorded (and warned about at dispatch) so window-size sweeps
    #   across client counts can detect they compared different windows


def sample_workload(key: jax.Array, cfg: SimConfig) -> Workload:
    """Synthetic request stream per §4's three size classes."""
    k_obj, k_cls, k_small, k_med, k_large = jax.random.split(key, 5)
    r = cfg.n_requests
    object_ids = jax.random.randint(k_obj, (r,), 0, 8 * cfg.n_servers,
                                    dtype=jnp.int32)
    small = jax.random.uniform(k_small, (r,), minval=cfg.small_lo,
                               maxval=cfg.small_hi)
    med = jax.random.uniform(k_med, (r,), minval=cfg.small_hi,
                             maxval=cfg.medium_hi)
    large = jax.random.uniform(k_large, (r,), minval=cfg.medium_hi,
                               maxval=cfg.large_hi)
    if cfg.workload == "small":
        lengths = small
    elif cfg.workload == "medium":
        lengths = med
    elif cfg.workload == "large":
        lengths = large
    else:  # mixed: uniform over the three classes
        cls = jax.random.randint(k_cls, (r,), 0, 3)
        lengths = jnp.where(cls == 0, small, jnp.where(cls == 1, med, large))
    return Workload(object_ids=object_ids, lengths=lengths.astype(jnp.float32),
                    valid=jnp.ones((r,), bool))


def mean_request_mb(cfg: SimConfig) -> float:
    """Expected request size per workload class (MB)."""
    return {
        "small": (cfg.small_lo + cfg.small_hi) / 2,
        "medium": (cfg.small_hi + cfg.medium_hi) / 2,
        "large": (cfg.medium_hi + cfg.large_hi) / 2,
        "mixed": ((cfg.small_lo + cfg.small_hi) / 2
                  + (cfg.small_hi + cfg.medium_hi) / 2
                  + (cfg.medium_hi + cfg.large_hi) / 2) / 3,
    }[cfg.workload]


def expected_server_load_mb(cfg: SimConfig) -> float:
    """Expected FINAL average per-server load from scheduling alone."""
    return cfg.n_requests * mean_request_mb(cfg) / cfg.n_servers


def initial_loads(key: jax.Array, cfg: SimConfig) -> Tuple[jax.Array, jax.Array]:
    """Normal initial loads + optional straggler injection (§4).

    Paper: stragglers carry '5 times more load compared with the average
    loads assigned on other storage servers' — i.e. the extra is scaled to
    the run's expected per-server load, not the (small) initial load.
    """
    k_norm, k_strag = jax.random.split(key)
    # FMA guard (DESIGN.md §9, the `window_decrements` clamp idiom):
    # a multiply DIRECTLY feeding the add may contract to an FMA, and
    # whether it does was observed to depend on the lowering context —
    # the vmapped (T,)-batched prep of §14 fused where the per-trial
    # lax.map shape did not, a 1-ulp drift breaking the batched ==
    # sequential prep contract.  min(max(x, -big), big) is a bit-exact
    # identity on any f32 the normal can produce, but the add's operand
    # is now a clamp, not a multiply, so no backend contracts it.
    big = jnp.float32(3.4e38)
    noise = cfg.init_load_std * jax.random.normal(k_norm, (cfg.n_servers,))
    loads = cfg.init_load_mean + jnp.minimum(jnp.maximum(noise, -big), big)
    loads = jnp.maximum(loads, 0.0)
    n_strag = int(round(cfg.straggler_frac * cfg.n_servers))
    mask = jnp.zeros((cfg.n_servers,), bool)
    if n_strag > 0:
        idx = jax.random.choice(k_strag, cfg.n_servers, (n_strag,),
                                replace=False)
        mask = mask.at[idx].set(True)
        extra = cfg.straggler_factor * expected_server_load_mb(cfg)
        # same guard: the injected extra is nonnegative by construction
        loads = loads + jnp.maximum(mask * extra, 0.0)
    return loads.astype(jnp.float32), mask


def absorb_initial_loads(state: SchedState, loads: jax.Array,
                         log_cfg: LogConfig) -> SchedState:
    """Fold known initial loads into the log: p_i ∝ (1/M)·e^{-l_i/λ}.

    This is the vectorized fixed point of applying Eq. (2) once per server
    for its initial load, then renormalizing — how a client that has been
    running for a while would see the cluster.  The math lives in
    `policy_core.absorb_probs`, whose `lane_sum` normalizer makes the
    batched (T, M) prep of DESIGN.md §14 associate bit-identically to
    this per-trial shape.
    """
    probs = policy_core.absorb_probs(loads, log_cfg.lam, state.n_servers)
    return state.with_rows(loads=loads.astype(jnp.float32),
                           probs=probs.astype(jnp.float32))


def resolve_window_dt(cfg: SimConfig, scn: ScenarioConfig) -> float:
    """Static virtual seconds per window.  Auto default: the time in which
    the healthy cluster in aggregate exactly drains one window's expected
    bytes — so balanced load stays bounded while stragglers accumulate."""
    if scn.window_dt is not None:
        return float(scn.window_dt)
    if scn.name == "static":
        return 0.0
    per_window_mb = cfg.window_size * mean_request_mb(cfg)
    return per_window_mb / (cfg.n_servers * scn.base_rate_mb_s)


def make_trace(key: jax.Array, cfg: SimConfig,
               scn: ScenarioConfig) -> ClusterTrace:
    """Build the scenario's rate-event schedule (static shapes per scenario).

    Straggler identities are drawn from ``key`` so every vmapped trial sees
    a different slow subset (matching the paper's per-trial straggler
    injection).  Event times are fractions of the stream horizon
    ``n_windows * window_dt``.
    """
    m = cfg.n_servers
    base = scn.base_rate_mb_s
    horizon = max(cfg.n_windows * resolve_window_dt(cfg, scn), 1e-6)
    base_row = jnp.full((m,), base, jnp.float32)

    if scn.name == "static":
        return ClusterTrace(times=jnp.zeros((1,), jnp.float32),
                            rates=base_row[None])

    if scn.name == "correlated_rack":
        rack = min(scn.rack_size, m)
        start = jax.random.randint(key, (), 0, m - rack + 1)
        idx = jnp.arange(m)
        mask = (idx >= start) & (idx < start + rack)
    else:
        n_strag = max(int(round(scn.straggler_frac * m)), 1)
        idx = jax.random.choice(key, m, (n_strag,), replace=False)
        mask = jnp.zeros((m,), bool).at[idx].set(True)
    slow_row = jnp.where(mask, base / scn.slow_factor, base).astype(jnp.float32)

    if scn.name == "permanent_slow":
        return ClusterTrace(times=jnp.zeros((1,), jnp.float32),
                            rates=slow_row[None])
    if scn.name == "transient":
        times = jnp.asarray([0.0, scn.onset * horizon, scn.recover * horizon],
                            jnp.float32)
        return ClusterTrace(times=times,
                            rates=jnp.stack([base_row, slow_row, base_row]))
    if scn.name == "flapping":
        n_ev = max(scn.n_flaps, 2)
        times = jnp.arange(n_ev, dtype=jnp.float32) * (horizon / n_ev)
        rows = jnp.stack([base_row if e % 2 == 0 else slow_row
                          for e in range(n_ev)])
        return ClusterTrace(times=times, rates=rows)
    if scn.name == "correlated_rack":
        times = jnp.asarray([0.0, scn.onset * horizon], jnp.float32)
        return ClusterTrace(times=times, rates=jnp.stack([base_row, slow_row]))
    raise AssertionError(scn.name)


def trace_straggler_mask(trace: ClusterTrace, scn: ScenarioConfig) -> jax.Array:
    """(M,) bool: servers that are slow at any point of the trace."""
    return jnp.any(trace.rates < scn.base_rate_mb_s * (1.0 - 1e-6), axis=0)


def _trial_setup(key: jax.Array, cfg: SimConfig, log_cfg: LogConfig):
    """Per-trial simulation inputs: (init_loads, straggler_mask, work,
    state, trace) — shared verbatim by the sequential and the trial-grid
    paths so both schedule bit-identical streams."""
    k_load, k_work, k_sched = jax.random.split(key, 3)
    init, strag_mask = initial_loads(k_load, cfg)
    work = sample_workload(k_work, cfg)
    state = statlog.init_state(log_cfg)
    state = absorb_initial_loads(state, init, log_cfg)
    trace = None
    if cfg.scenario is not None:
        # fold_in keeps the 3-way split above byte-identical to the static
        # path, so the degenerate trace reproduces it bit-for-bit.
        trace = make_trace(jax.random.fold_in(key, 0x7e3), cfg, cfg.scenario)
        state = state._replace(rates=trace.rates[0])
    return init, strag_mask, work, state, trace, k_sched


def _trial_result(cfg: SimConfig, window_dt: float, init, strag_mask, work,
                  trace, chosen, probe_msgs, redirected, latencies,
                  window_loads,
                  phase_time: Optional[jax.Array] = None,
                  window_size_eff: Optional[int] = None) -> TrialResult:
    """Fold one scheduled stream into the TrialResult bookkeeping — the
    ONE post step every client-model x backend combination shares.

    ``phase_time`` overrides the host-side makespan reduction — the
    kernel paths pass the fused in-VMEM metric (bit-equal: ``max`` is
    order-free and grouped steps share their duplicates' latency), the
    per_client jax path the masked cross-client max.

    The f32 per-server sum goes through `policy_core.server_segment_sum`
    (pinned one-hot + tree_sum association, DESIGN.md §14) so the
    batched (T, R) post and this per-trial shape produce bit-identical
    loads; the integer request count keeps the backend ``segment_sum``
    (integer adds are exact under any association)."""
    written = policy_core.server_segment_sum(work.lengths, chosen,
                                             cfg.n_servers)
    n_assigned = jax.ops.segment_sum(jnp.ones_like(chosen), chosen,
                                     num_segments=cfg.n_servers)
    if cfg.scenario is not None:
        strag_mask = strag_mask | trace_straggler_mask(trace, cfg.scenario)
    # integer sum: hit counts are backend-invariant under any association
    hits = jnp.sum(strag_mask[chosen].astype(jnp.int32))
    if phase_time is None:
        # completion estimate = window open time + queueing latency.
        # max(·, 0) is the §9 FMA guard (a window open time is
        # nonnegative by construction): the add's operand must not be a
        # multiply, or the batched §14 post contracts it where the
        # sequential shape does not.
        w_open = jnp.maximum(
            (jnp.arange(cfg.n_requests) // cfg.window_size).astype(
                jnp.float32) * jnp.float32(window_dt), 0.0)
        completion = w_open + latencies
        phase_time = jnp.max(completion)
    if window_size_eff is None:
        window_size_eff = cfg.window_size
    return TrialResult(server_loads=init + written, n_assigned=n_assigned,
                       chosen=chosen, probe_msgs=probe_msgs,
                       straggler_hits=hits,
                       redirected=jnp.sum(redirected.astype(jnp.int32)),
                       init_loads=init, straggler_mask=strag_mask,
                       latencies=latencies,
                       phase_time=phase_time,
                       window_loads=window_loads,
                       window_size_eff=jnp.int32(window_size_eff))


def _observe(cfg: SimConfig) -> bool:
    # the degenerate static scenario must stay bit-identical to the
    # no-trace path for EVERY policy, so its completion feedback is off
    # (the static model never observes)
    return cfg.scenario is not None and cfg.scenario.name != "static"


def run_one_trial(key: jax.Array, cfg: SimConfig, policy: PolicyConfig,
                  log_cfg: LogConfig) -> TrialResult:
    """Sequential single-trial REFERENCE: `_trial_setup` + ONE
    `engine.run_stream` + `_trial_result`, all at unbatched shapes.

    `run_trials` never calls this — every client_model x backend combo
    dispatches through `_run_batched` — it is the comparator that parity
    tests and benchmarks ``lax.map`` over to prove the batched dispatch
    is bit-exact trial by trial (with ``cfg.backend == "kernel"`` it is
    the SEQUENTIAL kernel path, one pallas_call per trial).  shared_log
    only: the per_client reference is the same engine vmapped over
    client slices, i.e. ``_run_batched`` on the jax backend."""
    if cfg.client_model != "shared_log":
        raise ValueError(
            "run_one_trial is the shared_log sequential reference; got "
            f"client_model={cfg.client_model!r} (use backend='jax' "
            "run_trials as the per_client comparator)")
    init, strag_mask, work, state, trace, k_sched = _trial_setup(key, cfg,
                                                                 log_cfg)
    window_dt = (resolve_window_dt(cfg, cfg.scenario)
                 if cfg.scenario is not None else 0.0)
    res = engine.run_stream(state, work, k_sched, policy=policy,
                            log_cfg=log_cfg, window_size=cfg.window_size,
                            group_steps=True, trace=trace,
                            window_dt=window_dt, observe=_observe(cfg),
                            backend=cfg.backend)
    return _trial_result(cfg, window_dt, init, strag_mask, work, trace,
                         res.chosen, res.probe_msgs, res.redirected,
                         res.latencies, res.window_loads)


def _client_split_shape(cfg: SimConfig) -> Tuple[int, int, int, int]:
    """(n_clients, per-client slice length, tail padding, effective
    window size) of the per_client request partition."""
    c = cfg.n_clients
    per = -(-cfg.n_requests // c)
    pad = c * per - cfg.n_requests
    win = min(cfg.window_size, per)
    return c, per, pad, win


def _split_clients(works: Workload, c: int, per: int, pad: int) -> Workload:
    """Partition (T, R) request streams into (T, C, per) client slices
    (tail padding invalid; trailing clients may be whole PHANTOMS that
    scheduled nothing when n_clients > n_requests)."""
    def sp(a, fill):
        if pad:
            a = jnp.concatenate(
                [a, jnp.full(a.shape[:-1] + (pad,), fill, a.dtype)],
                axis=-1)
        return a.reshape(a.shape[:-1] + (c, per))

    return Workload(object_ids=sp(works.object_ids, 0),
                    lengths=sp(works.lengths, 0),
                    valid=sp(works.valid, False))


def _resolved_window_dt(cfg: SimConfig) -> float:
    return (resolve_window_dt(cfg, cfg.scenario)
            if cfg.scenario is not None else 0.0)


def _prep_trials(keys: jax.Array, cfg: SimConfig, log_cfg: LogConfig):
    """Stage 1 of the batched pipeline (DESIGN.md §14): per-trial
    simulation inputs for the whole (T,) key batch in ONE traced
    program.

    ``cfg.prep == "batched"`` vmaps `_trial_setup`; the shape-sensitive
    reduction inside (the Eq. (2) absorb normalizer) goes through
    `policy_core.absorb_probs`, whose `lane_sum` halving tree is
    batch-shape-invariant, so the vmapped tables are bit-identical to
    ``"sequential"`` — the ``lax.map`` escape hatch that traces each
    trial at the exact per-trial shapes of `run_one_trial` (the parity
    oracle, asserted in tests/test_simulate.py)."""
    one = lambda k: _trial_setup(k, cfg, log_cfg)  # noqa: E731
    if cfg.prep == "sequential":
        return jax.lax.map(one, keys)
    out = jax.vmap(one)(keys)
    # fusion fence (DESIGN.md §14): without it XLA fuses downstream
    # scheduling ops INTO the vmapped setup graph, and the changed
    # fusion context was observed to alter the codegen of the setup's
    # transcendentals (the absorb exp / the normal's erfinv) by 1 ulp
    # vs the sequential oracle — whose scan loop boundary is an
    # implicit fence.  The barrier makes the batched stage the same
    # isolated compilation unit the scan body is.
    return jax.lax.optimization_barrier(out)


def _sched_trials(cfg: SimConfig, policy: PolicyConfig, log_cfg: LogConfig,
                  works: Workload, states, k_sched: jax.Array, traces):
    """Stage 2 of the batched pipeline: the scheduling dispatch + the
    cross-client fold, (T,)-batched throughout.

    ONE pallas_call for the kernel backend (trial grid, or the 2-D
    trials x clients grid under per_client), the vmapped lax.scan
    engine for the jax backend, the shard_map'd sweep when
    ``cfg.mesh_shape`` is set.

    per_client (the contention model): each trial's request stream is
    partitioned over ``n_clients`` private logs that share the trial's
    initial-load snapshot and trace but never see each other's
    decisions; the per-stream window size CLAMPS to the slice length
    (``window_size_eff`` in the result, warned about at dispatch), and
    every cross-client aggregate — window_loads mean, probe sum, phase
    makespan — masks phantom clients and merges with the
    `policy_core.masked_client_sum` association, so the kernel path's
    merge is bit-identical to the jax path's.

    Returns ``(chosen, probes, redirected, latencies, wl, phase)`` in
    original request order; ``phase`` is None when no fused/folded
    makespan exists (shared_log jax) and `_post_trials` reduces it
    host-side."""
    per_client = cfg.client_model == "per_client"
    window_dt = _resolved_window_dt(cfg)
    observe = _observe(cfg)
    t = k_sched.shape[0]

    if per_client:
        c, per, pad, win = _client_split_shape(cfg)
        if win < cfg.window_size:
            warnings.warn(
                f"per_client window clamp: window_size={cfg.window_size} "
                f"exceeds the per-client slice (n_requests="
                f"{cfg.n_requests} over n_clients={c} -> {per}/client); "
                f"scheduling with window_size_eff={win} — sweeps "
                "comparing window sizes across client counts are "
                "comparing different windows", stacklevel=2)
        # each trial's stream split into its clients' slices: the first
        # of the engine's input splits, scoped with them
        with tune_profile.stage("engine_prep"):
            run_works = _split_clients(works, c, per, pad)
            run_keys = jax.vmap(lambda k: jax.random.split(k, c))(k_sched)
            run_states = jax.tree.map(
                lambda a: jnp.broadcast_to(a[:, None],
                                           (t, c) + a.shape[1:]),
                states)
    else:
        win = cfg.window_size
        run_works, run_keys, run_states = works, k_sched, states

    # THE tuned-tile resolution point (DESIGN.md §16): resolve the
    # (trial_tile, client_tile) pair ONCE — whichever mode cfg.tiles
    # selects — and thread the explicit ints through every dispatch
    # below (sweep, kernel grid, and the jax path's cross-client fold),
    # so all layers consume identical tiles and the association contract
    # holds no matter where the values came from.
    n_dev = 1
    if cfg.mesh_shape is not None:
        for s in cfg.mesh_shape:
            n_dev *= int(s)
    eff_tt, eff_ct = tune_table.resolve_sim_tiles(
        mode=cfg.tiles, policy=policy.name, backend=cfg.backend,
        n_servers=cfg.n_servers, n_requests=cfg.n_requests,
        n_clients=(c if per_client else 1), n_trials=t,
        window_size=cfg.window_size, device_count=n_dev,
        form=("grid" if per_client else "batch"),
        trial_tile=cfg.trial_tile, client_tile=cfg.client_tile)

    metrics = merged = smerge = None
    if cfg.mesh_shape is not None:
        # sharded sweep: the same dispatch wrapped in shard_map over the
        # sweep mesh, cross-client merges lifted to collectives
        # (parallel/sweep.py, DESIGN.md §12)
        from repro.parallel import sweep
        res, metrics, smerge = sweep.run_sweep(
            run_states, run_works, run_keys, mesh_shape=cfg.mesh_shape,
            policy=policy, log_cfg=log_cfg, window_size=win,
            backend=cfg.backend, group_steps=True, traces=traces,
            window_dt=window_dt, observe=observe,
            trial_tile=eff_tt, client_tile=eff_ct)
    elif cfg.backend == "kernel":
        res, metrics, merged = engine.run_stream_batch(
            run_states, run_works, run_keys, policy=policy,
            log_cfg=log_cfg, window_size=win, group_steps=True,
            traces=traces, window_dt=window_dt, observe=observe,
            trial_tile=eff_tt, client_tile=eff_ct)
    else:
        res, _, _ = engine.run_stream_batch(
            run_states, run_works, run_keys, policy=policy,
            log_cfg=log_cfg, window_size=win, group_steps=True,
            traces=traces, window_dt=window_dt, observe=observe,
            backend="jax")

    if per_client:
        # cross-client fold: true loads are the cross-client sums (the
        # request order is the original stream), the contention
        # aggregates the masked merges over REAL clients
        with tune_profile.stage("merge"):
            r = cfg.n_requests
            ct = eff_ct      # the resolved association width (see above)
            cvalid = jnp.any(run_works.valid, axis=-1)       # (T, C)
            chosen = res.chosen.reshape(t, c * per)[:, :r]
            redirected = res.redirected.reshape(t, c * per)[:, :r]
            latencies = res.latencies.reshape(t, c * per)[:, :r]
            probes = jnp.sum(jnp.where(cvalid, res.probe_msgs, 0),
                             axis=-1).astype(jnp.int32)
            if smerge is not None:
                # the sharded sweep's collective merge
                # (parallel/sweep.py): already the global mean/max/sum
                # rows, uniform across backends
                wl = smerge.window_loads_mean
                phase = smerge.phase_time
                probes = smerge.probe_msgs
            elif merged is not None:
                # the 2-D grid kernel path's merge (bit-identical to the
                # jax branch below — asserted in tests/test_simulate.py)
                wl = merged.window_loads_mean
                phase = merged.metrics[:, policy_core.MET_MAKESPAN]
            else:
                wl = jax.vmap(
                    lambda w, v: policy_core.masked_client_mean(w, v, ct)
                )(res.window_loads, cvalid)
                w_open = ((jnp.arange(per) // win).astype(jnp.float32)
                          * jnp.float32(window_dt))
                comp = jnp.where(run_works.valid,
                                 w_open[None, None, :] + res.latencies,
                                 0.0)
                phase = jnp.max(comp, axis=(1, 2))
    else:
        chosen, redirected = res.chosen, res.redirected
        latencies, probes, wl = res.latencies, res.probe_msgs, \
            res.window_loads
        phase = (metrics[:, policy_core.MET_MAKESPAN]
                 if metrics is not None else None)
    return chosen, probes, redirected, latencies, wl, phase


def _post_trials(cfg: SimConfig, init, strag_mask, works: Workload, traces,
                 chosen, probes, redirected, latencies, wl,
                 phase) -> TrialResult:
    """Stage 3 of the batched pipeline: the whole (T,) TrialResult stack
    from one traced `_trial_result` program.

    ``cfg.prep == "batched"`` vmaps it; every op inside is exact under
    batching — gathers, bool masks, integer segment sums, order-free
    maxes — except the f32 per-server load sum, which goes through the
    pinned `policy_core.server_segment_sum` association, so the stack is
    bit-identical to the ``"sequential"`` ``lax.map`` oracle."""
    window_dt = _resolved_window_dt(cfg)
    win = (_client_split_shape(cfg)[3]
           if cfg.client_model == "per_client" else cfg.window_size)
    xs = (init, strag_mask, works, traces, chosen, probes, redirected,
          latencies, wl)
    if phase is not None:
        one = lambda x: _trial_result(  # noqa: E731
            cfg, window_dt, *x[:-1], phase_time=x[-1], window_size_eff=win)
        xs = xs + (phase,)
    else:
        one = lambda x: _trial_result(  # noqa: E731
            cfg, window_dt, *x, window_size_eff=win)
    if cfg.prep == "sequential":
        return jax.lax.map(one, xs)
    # fusion fence on the INPUT side (same §14 story as `_prep_trials`):
    # keeps the scheduling stage's producers from fusing into the
    # vmapped bookkeeping graph, matching the sequential oracle's scan
    # loop boundary.
    return jax.vmap(one)(jax.lax.optimization_barrier(xs))


def _run_batched(keys: jax.Array, cfg: SimConfig, policy: PolicyConfig,
                 log_cfg: LogConfig) -> TrialResult:
    """THE trial runner: one batched dispatch for every client_model x
    backend combination (DESIGN.md §9/§11), composed from the three
    (T,)-batched pipeline stages (DESIGN.md §14) — `_prep_trials`
    (workloads / initial loads / absorbed tables / traces),
    `_sched_trials` (the scheduling dispatch + cross-client fold) and
    `_post_trials` (the TrialResult bookkeeping stack).  Each stage is
    independently jittable with ``cfg``/``policy``/``log_cfg`` static,
    which is how `benchmarks/sched_perf.py` times the prep/sched/post
    phase breakdown.

    The `repro.tune.profile.stage` wrappers are name scopes: every
    compiled op carries its stage path (``prep``, ``sched/...``,
    ``post``) in its ``op_name`` metadata, and a device trace splits the
    sweep's device time by them (DESIGN.md §16).  They change nothing
    in the compiled program and cost nothing at run time."""
    with tune_profile.stage("prep"):
        prep = _prep_trials(keys, cfg, log_cfg)
    init, strag_mask, works, states, traces, k_sched = prep
    with tune_profile.stage("sched"):
        sched = _sched_trials(cfg, policy, log_cfg, works, states, k_sched,
                              traces)
    with tune_profile.stage("post"):
        return _post_trials(cfg, init, strag_mask, works, traces, *sched)


@functools.partial(jax.jit, static_argnames=("cfg", "policy", "log_cfg"))
def run_trials(key: jax.Array, cfg: SimConfig, policy: PolicyConfig,
               log_cfg: LogConfig) -> TrialResult:
    """Run ``cfg.n_trials`` independent trials (one batched dispatch,
    jitted).

    Every client_model x backend combination goes through the SAME
    `_run_batched` runner.  The kernel backend runs the WHOLE sweep as
    one pallas_call — grid = trial tiles for shared_log (DESIGN.md §9),
    ``(trial tiles, client tiles)`` for the per_client contention model
    (DESIGN.md §11) — with per-trial makespan fused in-VMEM (under
    per_client the cross-client merges follow the kernel, §17); every
    §3.4 policy dispatches through it since the in-VMEM sorts of
    DESIGN.md §10.  Decisions,
    latencies, loads, window_loads and phase_time are bit-exact vs.
    mapping the sequential kernel path trial by trial AND vs. the
    vmapped jax engine (asserted in tests/test_kernels.py and
    tests/test_simulate.py)."""
    policies.validate_policy(policy, cfg.n_servers)
    with tune_profile.stage("prep"):
        keys = jax.random.split(key, cfg.n_trials)
    return _run_batched(keys, cfg, policy, log_cfg)


def default_log_cfg(cfg: SimConfig, lam: Optional[float] = None) -> LogConfig:
    """λ on the order of the expected per-server load so Eq. (2)'s
    exponential stays in a resolvable range over the whole run
    (DESIGN.md numerical-fidelity note; λ -> 0 recovers the literal
    paper behaviour)."""
    if lam is None:
        lam = max(4.0 * mean_request_mb(cfg), expected_server_load_mb(cfg))
    return LogConfig(n_servers=cfg.n_servers, lam=lam)


def run_scenario_eval(seed: int = 0, cfg: Optional[SimConfig] = None,
                      scenario_names: Tuple[str, ...] = SCENARIOS,
                      policy_names: Tuple[str, ...] = SWEEP_POLICIES,
                      threshold: float = 5.0,
                      ect_threshold: float = 0.05,
                      scenario: Optional[ScenarioConfig] = None) -> dict:
    """Temporal sweep: {scenario: {policy: TrialResult}}, all jitted.

    ``threshold`` is in MB (load benefit) for the paper's policies; the
    rate-aware ECT guard is in expected *seconds*, hence the separate
    ``ect_threshold``.  ``scenario`` overrides the per-name defaults'
    common knobs (rates, straggler fraction, ...).
    """
    cfg = cfg or SimConfig()
    base_scn = scenario or ScenarioConfig()
    key = jax.random.key(seed)
    out: dict = {}
    for scn_name in scenario_names:
        scn_cfg = dataclasses.replace(base_scn, name=scn_name)
        s_cfg = dataclasses.replace(cfg, scenario=scn_cfg)
        log_cfg = default_log_cfg(s_cfg)
        row = {}
        for name in policy_names:
            thr = ect_threshold if name == "ect" else threshold
            pol = PolicyConfig(name=name, threshold=thr)
            row[name] = run_trials(key, s_cfg, pol, log_cfg)
        out[scn_name] = row
    return out


def run_paper_eval(seed: int = 0, cfg: Optional[SimConfig] = None,
                   policy_names: Tuple[str, ...] = ("rr", "mlml", "trh",
                                                    "nltr", "two_choice"),
                   threshold: float = 5.0,
                   nltr_ns: Tuple[int, ...] = (1, 2)) -> dict:
    """Run the full §4 evaluation; returns {label: TrialResult}."""
    cfg = cfg or SimConfig()
    log_cfg = default_log_cfg(cfg)
    key = jax.random.key(seed)
    out = {}
    for name in policy_names:
        if name == "nltr":
            for n in nltr_ns:
                pol = PolicyConfig(name="nltr", threshold=threshold, nltr_n=n)
                out[f"{n}ltr"] = run_trials(key, cfg, pol, log_cfg)
        else:
            pol = PolicyConfig(name=name, threshold=threshold)
            out[name] = run_trials(key, cfg, pol, log_cfg)
    return out
