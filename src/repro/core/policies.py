"""Scheduling policies of the log-assisted straggler-aware I/O scheduler.

Implements the paper's §3.4 algorithms plus two baselines:

* ``rr``         — round-robin (paper baseline): ``server = object_id mod M``.
* ``mlml``       — Max Length - Min Load (Alg. 1): length-sorted requests are
                   paired circularly with probability-sorted servers.
* ``trh``        — Two Random from Top Half (Alg. 2): power-of-two-choices
                   restricted to the lightest M/2 servers of the log.
* ``nltr``       — n-Level Two Random (Alg. 3): servers split into K = 2^n
                   equal sections (by middle), requests split into K sections
                   (by recursive average); two random choices inside the
                   matching section.
* ``two_choice`` — the authors' prior SC'14 probing scheduler [18]: probe the
                   default server + one random server, take the lighter.
                   Costs 2 probe messages per request (counted by the engine)
                   — the overhead this paper's log removes.
* ``ect``        — beyond-paper extension: pick argmin of expected completion
                   time ``(load_i + len) / rate_i`` using the EWMA service
                   rate observed from completions.  Sees *slow* servers, not
                   just *loaded* ones.  Documented in DESIGN.md.

Each policy exists in two forms that are cross-validated in tests:

* a pure-JAX form — ``plan_window`` (per-window sorting / sectioning) +
  ``select_target`` (per-request decision inside a ``lax.scan``), and
* ``HostScheduler`` — a numpy twin used on the real I/O request hot path.

All policies except ``rr`` are guarded by the paper's user threshold: the
redirect only happens when ``load(default) - load(target) > threshold``
(prose of §3.4.1; the printed pseudocode has the branch inverted by an OCR
artifact — we follow the prose).
"""

from __future__ import annotations

import dataclasses
from typing import NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import policy_core, statlog
from repro.core.statlog import LogConfig, SchedState

POLICIES = ("rr", "mlml", "trh", "nltr", "two_choice", "ect")

# Baseline probe RPCs per scheduled request (paper defaults).  This is
# the quantity the paper's log design eliminates (§1, §5).  The
# authoritative per-config count is ``PolicyConfig.probes_per_request``,
# which derives from ``probe_choices`` so the engine and the host twin
# can never drift apart (cross-checked in tests/test_policies.py).
PROBES_PER_REQUEST = {
    "rr": 0,
    "mlml": 0,
    "trh": 0,
    "nltr": 0,
    "ect": 0,
    "two_choice": 2,
}

RNG_IMPLS = ("jax", "lcg")


@dataclasses.dataclass(frozen=True)
class PolicyConfig:
    """Static configuration of a scheduling policy."""

    name: str = "trh"
    threshold: float = 0.0      # MB of load benefit required to redirect
    nltr_n: int = 2             # n of nLTR; K = 2**n sections
    # two_choice only: number of candidate servers probed (paper uses 2).
    probe_choices: int = 2
    # Randomness source for the two-random draws: "jax" (threefry keys,
    # the default and the PR-1-compatible behaviour) or "lcg" (the Pallas
    # kernel's in-VMEM LCG, `policy_core.two_random_draws`) — the engine's
    # backend="kernel" parity mode.  Deterministic policies ignore it.
    rng: str = "jax"

    def __post_init__(self):
        if self.name not in POLICIES:
            raise ValueError(f"unknown policy {self.name!r}; choose from {POLICIES}")
        if self.name == "nltr" and not (1 <= self.nltr_n <= 6):
            raise ValueError("nltr_n must be in [1, 6]")
        if self.rng not in RNG_IMPLS:
            raise ValueError(f"rng must be one of {RNG_IMPLS}")

    @property
    def k_sections(self) -> int:
        return 2 ** self.nltr_n

    @property
    def probes_per_request(self) -> int:
        """Probe RPCs per request — derived from ``probe_choices`` (one
        probe per candidate server) so engine accounting and the host
        twin's ``probe_messages`` counter agree by construction."""
        return self.probe_choices if self.name == "two_choice" else 0


class WindowPlan(NamedTuple):
    """Window-start snapshot used by the per-request selection.

    The paper sorts servers (and, for MLML/nLTR, requests) once per time
    window (Algs. 1-3 all hoist ``sort`` out of the scheduling loop); loads
    consulted *inside* the loop are live.
    """

    order: jax.Array           # (R,) request processing order (perm of arange)
    sorted_servers: jax.Array  # (M,) server ids, lightest (highest prob) first
    req_section: jax.Array     # (R,) int32 nLTR section id per request, in
    #                            processing order (zeros for other policies)
    sec_size: int              # static servers-per-section (M // K)


def validate_policy(cfg: PolicyConfig, n_servers: int) -> None:
    """Cross-field validation a :class:`PolicyConfig` alone cannot do.

    nLTR splits the server list into ``K = 2**nltr_n`` sections of
    ``max(M // K, 1)`` servers; with ``K > M`` the integer division
    collapses every section onto the same server range and the policy
    silently degenerates to (clamped) single-server picks.  Raise at the
    dispatch boundary instead (engine / simulator / host scheduler), with
    both offending values named.  ``K == M`` (one server per section) is
    the legal edge.
    """
    if cfg.name == "nltr" and cfg.k_sections > n_servers:
        raise ValueError(
            f"nltr needs 2**nltr_n <= n_servers: nltr_n={cfg.nltr_n} gives "
            f"K={cfg.k_sections} sections for n_servers={n_servers} "
            "(sections would collapse onto the same server range)")


def _recursive_average_boundaries(sorted_len: jax.Array, valid: jax.Array,
                                  n_levels: int) -> jax.Array:
    """Split a desc-sorted length list into 2^n sections by recursive average.

    Returns (K-1,) boundary *indices* into the sorted list: section ``s`` of
    request position ``k`` is ``sum(boundaries <= k)`` (order-free, so the
    tree-order output of the shared core needs no sort).  The paper
    (§3.4.3) uses the *average* element to divide requests ("to better
    utilize the size factor") versus the *middle* element for servers.
    Delegates to `policy_core.recursive_average_bounds` — the single
    implementation the oracle and the Pallas kernel tile form also run,
    lane_sum-associated so all three layers compute identical bounds.
    """
    nvalid = jnp.sum(valid).astype(jnp.int32).reshape(1)
    return policy_core.recursive_average_bounds(sorted_len, nvalid, n_levels)


# policies whose window plan processes requests out of arrival order
REORDERING_POLICIES = ("mlml", "nltr")


def plan_window(cfg: PolicyConfig, state: SchedState, object_ids: jax.Array,
                lengths: jax.Array, valid: jax.Array) -> WindowPlan:
    """Build the window-start plan (sorts + sections) for a policy.

    The engine keeps XLA's stable ``argsort`` + take (fast on the scan
    hot path); the Pallas kernel runs `policy_core.rank_desc` +
    `permute_to_sorted` in-VMEM — the §13 fast path: one all-pairs
    comparison and masked-sum permutation applies, no sort network.
    Both order by (key desc, index asc) — a STRICT TOTAL order, so the
    permutation is unique and the two agree bit-for-bit by construction
    (property-pinned in tests/test_policies.py; DESIGN.md §10/§13).
    """
    r = object_ids.shape[0]
    m = state.n_servers
    # Servers sorted by probability desc == lightest first (paper Fig. 9/10).
    # contract-ok: CC-SORT engine keeps backend argsort; kernel twin is rank_desc (§10)
    sorted_servers = jnp.argsort(-state.probs).astype(jnp.int32)

    if cfg.name in REORDERING_POLICIES:
        # Requests processed in length-desc order; invalid (padding) rows sink
        # to the end via -inf keys.
        key_len = jnp.where(valid, lengths, -jnp.inf)
        # contract-ok: CC-SORT engine keeps backend argsort; kernel twin is rank_desc (§13)
        order = jnp.argsort(-key_len).astype(jnp.int32)
    else:
        order = jnp.arange(r, dtype=jnp.int32)

    if cfg.name == "nltr":
        k = cfg.k_sections
        sorted_len = lengths[order]
        sorted_valid = valid[order]
        bounds = _recursive_average_boundaries(sorted_len, sorted_valid, cfg.nltr_n)
        pos = jnp.arange(r, dtype=jnp.int32)
        req_section = jnp.sum(pos[:, None] >= bounds[None, :], axis=1).astype(jnp.int32)
        req_section = jnp.clip(req_section, 0, k - 1)
        sec_size = max(m // k, 1)
    else:
        req_section = jnp.zeros((r,), jnp.int32)
        sec_size = m

    return WindowPlan(order=order, sorted_servers=sorted_servers,
                      req_section=req_section, sec_size=sec_size)


def _two_random_min_load(state: SchedState, sorted_servers: jax.Array,
                         lo: jax.Array, size, key: jax.Array) -> jax.Array:
    """Pick 2 uniform positions in [lo, lo+size) of the sorted list, return
    the id with the smaller *live* load (Algs. 2-3 inner step)."""
    k1, k2 = jax.random.split(key)
    i1 = jax.random.randint(k1, (), 0, size) + lo
    i2 = jax.random.randint(k2, (), 0, size) + lo
    s1 = sorted_servers[i1]
    s2 = sorted_servers[i2]
    return jnp.where(state.loads[s1] <= state.loads[s2], s1, s2).astype(jnp.int32)


def select_target(cfg: PolicyConfig, plan: WindowPlan, state: SchedState,
                  pos: jax.Array, object_id: jax.Array, length: jax.Array,
                  key: jax.Array) -> jax.Array:
    """Per-request target server (before the threshold guard).

    ``pos`` is the request's position in the window processing order (used
    by MLML's circular pairing).  Live ``state.loads`` break two-random ties.
    """
    m = state.n_servers
    default = (object_id % m).astype(jnp.int32)

    if cfg.name == "rr":
        return default
    if cfg.name == "mlml":
        # k-th longest request -> k-th lightest server, circularly (Alg. 1).
        return plan.sorted_servers[pos % m]
    if cfg.name == "trh":
        half = max(m // 2, 1)
        return _two_random_min_load(state, plan.sorted_servers,
                                    jnp.asarray(0, jnp.int32), half, key)
    if cfg.name == "nltr":
        sec = plan.req_section[pos]
        lo = sec * plan.sec_size
        return _two_random_min_load(state, plan.sorted_servers, lo,
                                    plan.sec_size, key)
    if cfg.name == "two_choice":
        # SC'14 baseline: probe default + (probe_choices-1) random others,
        # take the lightest by live load.  Probes counted by the engine.
        keys = jax.random.split(key, cfg.probe_choices - 1)
        cand = [default]
        for i in range(cfg.probe_choices - 1):
            cand.append(jax.random.randint(keys[i], (), 0, m).astype(jnp.int32))
        cand = jnp.stack(cand)
        return cand[jnp.argmin(state.loads[cand])].astype(jnp.int32)
    if cfg.name == "ect":
        # Scored on the client-ESTIMATED rates row (observations only),
        # never the true trace rates — the stale-view contract.
        ect = policy_core.ect_scores(state.loads, state.est_rates, length)
        return jnp.argmin(ect).astype(jnp.int32)
    raise AssertionError(cfg.name)


def select_target_rng(cfg: PolicyConfig, plan: WindowPlan, state: SchedState,
                      pos: jax.Array, object_id: jax.Array, length: jax.Array,
                      key: jax.Array, rng: jax.Array
                      ) -> tuple[jax.Array, jax.Array]:
    """Like :func:`select_target`, but threading the kernel-compatible
    uint32 LCG state.  With ``cfg.rng == "lcg"`` the two-random policies
    consume `policy_core.two_random_draws` exactly as the Pallas kernel
    does (the draws advance on EVERY request, valid or padding, matching
    the kernel's unconditional stream); otherwise the jax key is used and
    ``rng`` passes through untouched."""
    if cfg.rng == "lcg" and cfg.name in ("trh", "nltr", "two_choice"):
        m = state.n_servers
        if cfg.name == "trh":
            half = max(m // 2, 1)
            i1, i2, rng = policy_core.two_random_draws(rng, half)
            s1 = plan.sorted_servers[i1]
            s2 = plan.sorted_servers[i2]
            target = jnp.where(state.loads[s1] <= state.loads[s2], s1,
                               s2).astype(jnp.int32)
            return target, rng
        if cfg.name == "nltr":
            sec = plan.req_section[pos]
            lo = sec * plan.sec_size
            i1, i2, rng = policy_core.two_random_draws(rng, plan.sec_size)
            s1 = plan.sorted_servers[lo + i1]
            s2 = plan.sorted_servers[lo + i2]
            target = jnp.where(state.loads[s1] <= state.loads[s2], s1,
                               s2).astype(jnp.int32)
            return target, rng
        # two_choice: default + (probe_choices-1) LCG-random candidates
        default = (object_id % m).astype(jnp.int32)
        cand = [default]
        for _ in range(cfg.probe_choices - 1):
            rng = policy_core.lcg_step(rng)
            cand.append(policy_core.lcg_mod(rng, m))
        cand = jnp.stack(cand)
        return cand[jnp.argmin(state.loads[cand])].astype(jnp.int32), rng
    return select_target(cfg, plan, state, pos, object_id, length, key), rng


def apply_threshold(cfg: PolicyConfig, state: SchedState, default: jax.Array,
                    target: jax.Array, length: jax.Array) -> jax.Array:
    """Paper's redirect guard: only redirect when the benefit exceeds the
    user threshold (§3.4.1 prose).  For the rate-aware ECT extension the
    benefit is in expected seconds (on the ESTIMATED rates), not bytes."""
    if cfg.name == "rr":
        return default
    benefit = policy_core.redirect_benefit(cfg.name, state.loads,
                                           state.est_rates, default, target,
                                           length)
    return jnp.where(benefit > cfg.threshold, target, default).astype(jnp.int32)


# ---------------------------------------------------------------------------
# Host-side (numpy) twin — the real I/O client hot path (repro.io.client).
# ---------------------------------------------------------------------------


class HostScheduler:
    """Numpy mirror of (plan_window, select_target, apply_threshold).

    Operates on a :class:`~repro.core.statlog.HostStatLog`.  A *window* is
    opened explicitly (:meth:`begin_window`) which snapshots the sorts, then
    :meth:`schedule` is called per request.  Cross-validated against the JAX
    engine in ``tests/test_policies.py``.
    """

    def __init__(self, cfg: PolicyConfig, log: statlog.HostStatLog,
                 seed: int = 0):
        validate_policy(cfg, log.n_servers)
        self.cfg = cfg
        self.log = log
        self.rng = np.random.default_rng(seed)
        self.probe_messages = 0
        self._sorted_servers: Optional[np.ndarray] = None
        self._masked: set[int] = set()

    # -- failure handling (used by repro.checkpoint retry logic) -----------
    def mask_server(self, server: int) -> None:
        """Exclude a failed server from future targets (until unmasked)."""
        self._masked.add(int(server))

    def unmask_server(self, server: int) -> None:
        self._masked.discard(int(server))

    @property
    def masked_servers(self) -> frozenset:
        return frozenset(self._masked)

    # -- window machinery ---------------------------------------------------
    def begin_window(self, lengths: Optional[Sequence[float]] = None) -> None:
        """Snapshot the window-start sorts.  Stable np.argsort == the
        kernel's §13 all-pairs rank (strict total order; DESIGN.md §10).
        ``lengths`` (all requests queued in this window) is needed by
        nLTR's request sectioning."""
        order = np.argsort(-self.log.probs, kind="stable")
        self._sorted_servers = order.astype(np.int64)
        self._pos = 0
        if self.cfg.name == "nltr" and lengths is not None and len(lengths):
            self._req_bounds = self._recursive_average_bounds(
                np.sort(np.asarray(lengths, np.float64))[::-1], self.cfg.nltr_n)
        else:
            self._req_bounds = None

    @staticmethod
    def _recursive_average_bounds(sorted_len: np.ndarray, n: int) -> np.ndarray:
        """Numpy twin of the engine's sectioning — the SAME shared core
        (`policy_core.recursive_average_bounds`, xp=np), all rows valid
        (the host scheduler sections the literal queued lengths)."""
        nvalid = np.asarray([len(sorted_len)], np.int32)
        return policy_core.recursive_average_bounds(
            np.ascontiguousarray(sorted_len), nvalid, n, xp=np)

    def _live_load(self, server: int) -> float:
        return self.log.loads[server]

    def _ect_rates(self) -> np.ndarray:
        """Client-estimated service rates: the packed table's est row,
        maintained by ``HostStatLog.observe_completion`` via the shared
        ``policy_core.observe_update`` (observations only — stale view)."""
        return self.log.est_rates

    def _two_random(self, lo: int, size: int) -> int:
        size = max(size, 1)
        ss = self._sorted_servers
        m = len(ss)
        cands = []
        for _ in range(8):  # rejection-sample around masked servers
            i1 = lo + int(self.rng.integers(0, size))
            i2 = lo + int(self.rng.integers(0, size))
            c1, c2 = int(ss[i1 % m]), int(ss[i2 % m])
            cands = [c for c in (c1, c2) if c not in self._masked]
            if cands:
                break
        if not cands:  # whole section masked: fall back to global lightest
            alive = [s for s in range(m) if s not in self._masked]
            return min(alive, key=self._live_load)
        return min(cands, key=self._live_load)

    def schedule(self, object_id: int, length_mb: float,
                 offset: int = 0) -> int:
        """Schedule one request; returns the chosen server and updates the
        log per Eqs. (1)-(3)."""
        if self._sorted_servers is None:
            self.begin_window()
        cfg, log = self.cfg, self.log
        m = log.n_servers
        default = int(object_id) % m
        pos = self._pos
        self._pos += 1
        log.record_request(object_id, offset, length_mb)

        if cfg.name == "rr":
            target = default
        elif cfg.name == "mlml":
            target = int(self._sorted_servers[pos % m])
        elif cfg.name == "trh":
            target = self._two_random(0, max(m // 2, 1))
        elif cfg.name == "nltr":
            k = cfg.k_sections
            if self._req_bounds is None:
                sec = 0
            else:
                sec = int((self._req_bounds <= pos).sum())
            sec = min(sec, k - 1)
            sec_size = max(m // k, 1)
            target = self._two_random(sec * sec_size, sec_size)
        elif cfg.name == "two_choice":
            cand = [default] + [int(self.rng.integers(0, m))
                                for _ in range(cfg.probe_choices - 1)]
            self.probe_messages += cfg.probe_choices
            cand = [c for c in cand if c not in self._masked] or cand
            target = min(cand, key=self._live_load)
        elif cfg.name == "ect":
            ect = policy_core.ect_scores(log.loads, self._ect_rates(),
                                         length_mb, xp=np)
            if self._masked:
                ect = ect.copy()
                ect[list(self._masked)] = np.inf
            target = int(np.argmin(ect))
        else:  # pragma: no cover
            raise AssertionError(cfg.name)

        if target in self._masked:
            alive = [s for s in range(m) if s not in self._masked]
            target = min(alive, key=self._live_load)
        if cfg.name != "rr" and default not in self._masked:
            benefit = policy_core.redirect_benefit(
                cfg.name, log.loads, self._ect_rates(), default, target,
                length_mb, xp=np)
            chosen = target if benefit > cfg.threshold else default
        else:
            chosen = target
        log.apply_assignment(chosen, length_mb)
        return chosen
