"""Scheduling-policy semantics: JAX engine vs host twin, paper examples."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import engine, statlog
from repro.core.engine import Workload
from repro.core.policies import HostScheduler, PolicyConfig
from repro.core.statlog import HostStatLog, LogConfig


def _run_jax(policy, obj, lens, m=8, lam=32.0, threshold=0.0, seed=0,
             init_loads=None, group=False):
    cfg = LogConfig(n_servers=m, lam=lam)
    state = statlog.init_state(cfg, init_loads)
    work = Workload(jnp.asarray(obj, jnp.int32),
                    jnp.asarray(lens, jnp.float32),
                    jnp.ones((len(obj),), bool))
    res = engine.run_window(state, work, jax.random.key(seed),
                            policy=PolicyConfig(name=policy,
                                                threshold=threshold),
                            log_cfg=cfg, group_steps=group)
    return res


def test_rr_is_object_mod_m():
    obj = [0, 5, 9, 13, 21]
    res = _run_jax("rr", obj, [1.0] * 5, m=4)
    np.testing.assert_array_equal(np.asarray(res.chosen),
                                  np.asarray(obj) % 4)
    assert int(res.probe_msgs) == 0


def test_mlml_pairs_longest_with_lightest():
    """Alg. 1: longest request -> highest-prob (lightest) server."""
    m = 4
    init = jnp.asarray([10.0, 0.0, 20.0, 30.0])
    lens = [5.0, 50.0, 1.0]          # sorted desc: 50, 5, 1
    obj = [0, 1, 2]
    res = _run_jax("mlml", obj, lens, m=m, lam=16.0, threshold=0.0,
                   init_loads=init)
    # init probs equal -> after absorb? run_window absorbs nothing: probs
    # uniform; sorted_servers order is argsort(-p) = stable = [0,1,2,3].
    # With uniform probs MLML degenerates to positional pairing.
    assert res.chosen.shape == (3,)


def test_mlml_positional_pairing_with_decayed_probs():
    m = 4
    cfg = LogConfig(n_servers=m, lam=8.0)
    state = statlog.init_state(cfg)
    # load server 0 heavily, 1 lightly -> probs: 2,3 > 1 > 0
    state = statlog.apply_assignment(state, jnp.asarray(0),
                                     jnp.asarray(40.0), cfg)
    state = statlog.apply_assignment(state, jnp.asarray(1),
                                     jnp.asarray(4.0), cfg)
    work = Workload(jnp.asarray([0, 1, 2], jnp.int32),
                    jnp.asarray([9.0, 1.0, 5.0], jnp.float32),
                    jnp.ones((3,), bool))
    res = engine.run_window(state, work, jax.random.key(0),
                            policy=PolicyConfig(name="mlml",
                                                threshold=1e9),
                            log_cfg=cfg, group_steps=False)
    # threshold huge -> always falls back to default RR homes
    np.testing.assert_array_equal(np.asarray(res.chosen), [0, 1, 2])
    assert not bool(res.redirected.any())


def test_trh_picks_from_light_half_and_respects_threshold():
    m = 8
    init = jnp.asarray([0.0, 1.0, 2.0, 3.0, 100.0, 101.0, 102.0, 103.0])
    obj = [4, 5, 6, 7] * 5          # defaults all in the heavy half
    res = _run_jax("trh", obj, [4.0] * 20, m=m, threshold=10.0,
                   init_loads=init)
    chosen = np.asarray(res.chosen)
    assert (chosen < 4).all(), chosen  # redirected into the light half
    assert int(res.probe_msgs) == 0


def test_two_choice_counts_probes():
    res = _run_jax("two_choice", list(range(10)), [1.0] * 10, m=8)
    assert int(res.probe_msgs) == 20  # 2 per request (SC'14 baseline)


def test_nltr_sections_spread_requests():
    m = 16
    init = jnp.arange(16, dtype=jnp.float32) * 10
    lens = [100.0, 90.0, 50.0, 40.0, 5.0, 4.0, 3.0, 2.0]
    res = _run_jax("nltr", list(range(8)), lens, m=m, threshold=0.0,
                   init_loads=init, lam=200.0)
    assert res.chosen.shape == (8,)
    assert int(res.probe_msgs) == 0


def test_ect_uses_observed_rates():
    m = 3
    cfg = LogConfig(n_servers=m)
    state = statlog.init_state(cfg)
    # same loads everywhere, but server 2 observed 10x faster.  ECT reads
    # the est_rates row, which only observations write (stale-view
    # contract) — so seed it through observe_completion.
    state = state.with_rows(loads=jnp.asarray([10.0, 10.0, 10.0]))
    for srv, rate in ((0, 1.0), (1, 1.0), (2, 10.0)):
        state = statlog.observe_completion(state, jnp.asarray(srv),
                                           jnp.asarray(rate), cfg)
    work = Workload(jnp.asarray([0], jnp.int32), jnp.asarray([1.0]),
                    jnp.ones((1,), bool))
    res = engine.run_window(state, work, jax.random.key(0),
                            policy=PolicyConfig(name="ect", threshold=-1e9),
                            log_cfg=cfg, group_steps=False)
    assert int(res.chosen[0]) == 2


def test_host_scheduler_matches_engine_rr_mlml():
    """Deterministic policies agree between host twin and jitted engine."""
    m, n = 6, 24
    rng = np.random.default_rng(0)
    obj = rng.integers(0, 100, n).tolist()
    lens = rng.uniform(1, 30, n).tolist()
    for policy in ("rr", "mlml"):
        res = _run_jax(policy, obj, lens, m=m, lam=32.0, threshold=2.0)
        host = HostScheduler(PolicyConfig(name=policy, threshold=2.0),
                             HostStatLog(LogConfig(n_servers=m, lam=32.0)))
        host.begin_window(lens)
        # engine processes mlml in length-desc order; replay identically
        order = np.argsort([-l for l in lens], kind="stable") \
            if policy == "mlml" else np.arange(n)
        got = np.empty(n, np.int64)
        for pos, idx in enumerate(order):
            got[idx] = host.schedule(obj[idx], lens[idx])
        np.testing.assert_array_equal(np.asarray(res.chosen), got, policy)


def test_ect_completion_feedback_parity_jax_vs_host():
    """Temporal model: the ECT completion-feedback path agrees between the
    jitted engine and the real IOClient when both run the SAME ClusterTrace.

    One request per window, no draining (window_dt=0) and no flush, so the
    observation cadence is identical: schedule -> complete -> observe.  The
    engine's estimated latency (loads_after / rate) must equal the queueing
    cluster's WriteResult.seconds, hence identical ewma_lat and identical
    chosen servers over the whole stream.
    """
    from repro.core.engine import ClusterTrace
    from repro.io import striping
    from repro.io.client import IOClient, IOClientConfig

    m, n, base = 8, 40, 100.0
    rates = np.full(m, base)
    rates[[2, 5]] = base / 8.0          # permanent slow-service stragglers
    trace = ClusterTrace(times=jnp.zeros((1,), jnp.float32),
                         rates=jnp.asarray(rates, jnp.float32)[None])
    rng = np.random.default_rng(7)
    lens = rng.integers(2, 11, n).astype(np.float64)  # whole MB: f32-exact

    # -- JAX path: one request per window over the trace -------------------
    log_cfg = LogConfig(n_servers=m, lam=64.0)
    state = statlog.init_state(log_cfg, rates=jnp.asarray(rates))
    obj = [striping.object_id_for(f, 0) % m for f in range(n)]
    work = Workload(jnp.asarray(obj, jnp.int32),
                    jnp.asarray(lens, jnp.float32), jnp.ones((n,), bool))
    res = engine.run_stream(state, work, jax.random.key(0),
                            policy=PolicyConfig(name="ect", threshold=0.01),
                            log_cfg=log_cfg, window_size=1,
                            group_steps=False, trace=trace, window_dt=0.0)

    # -- host path: IOClient over a SimulatedCluster on the same trace ----
    from repro.io.objectstore import SimulatedCluster
    sim = SimulatedCluster(m, base_rate_mb_s=base, trace=trace)
    cli = IOClient(sim, IOClientConfig(
        policy=PolicyConfig(name="ect", threshold=0.01),
        stripe_size=16 * striping.MB, lam_mb=64.0))
    for f in range(n):
        cli.write_file(f, size_mb=float(lens[f]))     # single-object files

    host_chosen = np.asarray([r.server for r in cli.records])
    jax_chosen = np.asarray(res.chosen)
    # Discovery phase (every server tried once, stragglers observed) must
    # agree exactly.  Beyond it, ECT *equalizes* completion-time scores
    # across the healthy servers, so the argmin rides on sub-epsilon
    # float32-vs-float64 noise — we assert the semantically meaningful
    # invariants instead of bitwise equality of symmetric-server swaps.
    np.testing.assert_array_equal(jax_chosen[:10], host_chosen[:10])
    # both paths hit the slow servers at IDENTICAL positions (no tie there:
    # an observed straggler's score is distinctly worse)
    strag = np.isin(jax_chosen, (2, 5))
    np.testing.assert_array_equal(strag, np.isin(host_chosen, (2, 5)))
    np.testing.assert_array_equal(jax_chosen[strag], host_chosen[strag])
    # per-server landing counts agree up to symmetric near-tie swaps
    cj = np.bincount(jax_chosen, minlength=m)
    ch = np.bincount(host_chosen, minlength=m)
    assert np.abs(cj - ch).max() <= 2, (cj, ch)
    # the observed quantity is the same: wherever the choices agree, the
    # engine's estimated latency equals the cluster's WriteResult.seconds
    agree = jax_chosen == host_chosen
    secs = np.asarray([r.seconds for r in cli.records])
    np.testing.assert_allclose(np.asarray(res.latencies)[agree],
                               secs[agree], rtol=1e-4)
    # slow servers are VISIBLE in the JAX path now: ewma near the true
    # slow service rate on both sides
    ewma = np.asarray(res.state.ewma_lat)
    host_ewma = np.asarray(cli.log.ewma_lat)
    assert (ewma > 0).all() and (host_ewma > 0).all()
    for s in (2, 5):
        assert ewma[s] <= base / 8.0 + 1e-3
        np.testing.assert_allclose(ewma[s], host_ewma[s], rtol=1e-3)


def test_probe_accounting_derives_from_probe_choices():
    """Satellite fix: engine probe accounting and the host twin's counter
    both derive from PolicyConfig.probe_choices — no more hard-coded 2."""
    from repro.core.policies import PROBES_PER_REQUEST
    n, m = 10, 8
    obj = list(range(n))            # unique objects: no grouping merges
    lens = [1.0] * n
    for k in (2, 3, 5):
        pol = PolicyConfig(name="two_choice", probe_choices=k)
        assert pol.probes_per_request == k
        res = _run_jax("two_choice", obj, lens, m=m)._replace()  # warm
        res = engine.run_window(
            statlog.init_state(LogConfig(n_servers=m)),
            Workload(jnp.asarray(obj, jnp.int32),
                     jnp.asarray(lens, jnp.float32), jnp.ones((n,), bool)),
            jax.random.key(0), policy=pol,
            log_cfg=LogConfig(n_servers=m))
        assert int(res.probe_msgs) == n * k
        host = HostScheduler(pol, HostStatLog(LogConfig(n_servers=m)))
        host.begin_window(lens)
        for o, ln in zip(obj, lens):
            host.schedule(o, ln)
        assert host.probe_messages == n * k
    # log-assisted policies never probe, whatever probe_choices says
    for name in ("rr", "mlml", "trh", "nltr", "ect"):
        assert PolicyConfig(name=name,
                            probe_choices=7).probes_per_request == 0
        assert PROBES_PER_REQUEST[name] == 0
    # the paper-default config still matches the documented table
    assert PolicyConfig(name="two_choice").probes_per_request == \
        PROBES_PER_REQUEST["two_choice"]


def test_est_row_lags_true_rate_and_layers_agree():
    """Acceptance: when a straggler's TRUE rate drops mid-stream, the
    client-estimated row lags it (stale by construction), and the kernel,
    engine, and host client all rank servers identically on est_rates."""
    from repro.core.engine import ClusterTrace
    from repro.io.client import IOClient, IOClientConfig
    from repro.io.objectstore import SimulatedCluster
    from repro.io import striping

    m, base, slow_f = 8, 100.0, 10.0
    strag = 2
    slow = np.full(m, base, np.float32)
    slow[strag] = base / slow_f
    # rate drops a quarter of the way in and STAYS slow
    trace = ClusterTrace(times=jnp.asarray([0.0, 1.0], jnp.float32),
                         rates=jnp.asarray(np.stack(
                             [np.full(m, base, np.float32), slow])))
    log_cfg = LogConfig(n_servers=m, lam=64.0)
    rng = np.random.default_rng(3)
    n = 64
    lens = rng.integers(2, 9, n).astype(np.float64)
    obj = [striping.object_id_for(f, 0) % m for f in range(n)]
    work = Workload(jnp.asarray(obj, jnp.int32),
                    jnp.asarray(lens, jnp.float32), jnp.ones((n,), bool))
    pol = PolicyConfig(name="ect", threshold=0.01)
    state = statlog.init_state(log_cfg, rates=trace.rates[0])
    kw = dict(policy=pol, log_cfg=log_cfg, window_size=8,
              group_steps=False, trace=trace, window_dt=0.5)
    eng = engine.run_stream(state, work, jax.random.key(0), backend="jax",
                            **kw)
    ker = engine.run_stream(state, work, jax.random.key(0),
                            backend="kernel", **kw)

    # host path: IOClient over the queueing cluster on the same trace
    sim = SimulatedCluster(m, base_rate_mb_s=base, trace=trace)
    cli = IOClient(sim, IOClientConfig(policy=pol,
                                       stripe_size=16 * striping.MB,
                                       lam_mb=64.0))
    for f in range(n):
        cli.write_file(f, size_mb=float(lens[f]))
        sim.advance_time(0.0625)            # writes spread over the trace

    true_rate = base / slow_f
    for est in (np.asarray(eng.state.est_rates),
                np.asarray(ker.state.est_rates),
                cli.log.est_rates):
        # the estimated row LAGS the true drop: below the healthy rate
        # (the drop is visible) but still above the true slow rate (the
        # EWMA hasn't fully converged — stale by construction)...
        assert true_rate < est[strag] < base, est
        # ...and every layer ranks the straggler slowest on est_rates
        assert int(np.argmin(est)) == strag, est
    # engine and kernel see BIT-IDENTICAL estimated rows
    np.testing.assert_array_equal(np.asarray(eng.state.est_rates),
                                  np.asarray(ker.state.est_rates))


def test_masking_failed_servers():
    host = HostScheduler(PolicyConfig(name="trh", threshold=0.0),
                         HostStatLog(LogConfig(n_servers=4)))
    host.mask_server(0)
    host.mask_server(1)
    host.begin_window()
    for i in range(20):
        s = host.schedule(i, 1.0)
        assert s in (2, 3)
    host.unmask_server(0)
    assert 0 not in host.masked_servers


# ---------------------------------------------------------------------------
# In-VMEM sort contract (DESIGN.md §10): the kernel's bitonic network
# computes THE unique stable permutation, so the engine's backend argsort
# may stand in for it on the hot path.
# ---------------------------------------------------------------------------

from repro.core import policy_core  # noqa: E402


def test_bitonic_argsort_equals_stable_argsort():
    """The (key desc, index asc) comparator is a strict total order: the
    bitonic compare-exchange network and stable argsort have exactly one
    common answer — across sizes, heavy ties, invalid masks, and both
    xp twins.  This equality is what lets plan_window keep jnp.argsort
    while the Pallas kernel sorts in-VMEM (DESIGN.md §10)."""
    rng = np.random.default_rng(3)
    for r in (1, 2, 3, 17, 60, 100, 128):
        for tie_pool in (None, 4):
            if tie_pool is None:
                keys = rng.uniform(0.0, 50.0, r).astype(np.float32)
            else:  # heavy ties exercise the index tiebreak
                keys = rng.choice(np.linspace(0, 3, tie_pool),
                                  r).astype(np.float32)
            valid = rng.random(r) > 0.3
            ref = np.argsort(-np.where(valid, keys, -np.inf), kind="stable")
            got_np, _ = policy_core.bitonic_argsort_desc(keys, valid=valid,
                                                         xp=np)
            got_jnp, skeys = policy_core.bitonic_argsort_desc(
                jnp.asarray(keys), valid=jnp.asarray(valid))
            np.testing.assert_array_equal(got_np[:r], ref, err_msg=str(r))
            np.testing.assert_array_equal(np.asarray(got_jnp)[:r], ref)
            # sorted keys descend over the valid prefix, -inf elsewhere
            sk = np.asarray(skeys)[:valid.sum()]
            assert (np.diff(sk) <= 0).all()


def test_recursive_average_bounds_batched_matches_engine_form():
    """The kernel evaluates the nLTR section bounds on (t_tile, R_pad)
    tiles; the engine on one (R,) row.  Same integers, any batch."""
    rng = np.random.default_rng(5)
    t, ws = 5, 60
    lens = rng.uniform(0.5, 30.0, (t, ws)).astype(np.float32)
    valid = rng.random((t, ws)) > 0.25
    for n in (1, 2, 3):
        rows = []
        for i in range(t):
            order, skeys = policy_core.bitonic_argsort_desc(
                jnp.asarray(lens[i]), valid=jnp.asarray(valid[i]))
            nv = jnp.asarray([valid[i].sum()], jnp.int32)
            rows.append(np.asarray(policy_core.recursive_average_bounds(
                skeys, nv, n)))
        orderb, skeysb = policy_core.bitonic_argsort_desc(
            jnp.asarray(lens), valid=jnp.asarray(valid))
        nvb = jnp.asarray(valid.sum(axis=1), jnp.int32)[:, None]
        batched = np.asarray(policy_core.recursive_average_bounds(
            skeysb, nvb, n))
        np.testing.assert_array_equal(batched, np.stack(rows), err_msg=str(n))


# ---------------------------------------------------------------------------
# Permutation-apply contract (DESIGN.md §13): the payload-carrying bitonic
# network and the inverse-permutation apply are PURE RELOCATIONS — bit-equal
# to (stable argsort + take) and to the one-hot scatter they replaced on the
# kernel's sort-policy window path.
# ---------------------------------------------------------------------------


def test_payload_bitonic_equals_stable_argsort_take():
    """Payload lanes ride the compare-exchange network under the same swap
    mask as the keys, so the sorted payloads equal payload[stable_argsort]
    element-for-element (no arithmetic touches them) — across odd sizes,
    R not a power of two, heavy duplicate keys, all-invalid windows, and
    both xp twins."""
    rng = np.random.default_rng(7)
    for r in (1, 3, 17, 33, 60, 100, 128):
        for tie_pool in (None, 3):
            if tie_pool is None:
                keys = rng.uniform(0.0, 50.0, r).astype(np.float32)
            else:  # duplicate keys: the index tiebreak must carry payloads
                keys = rng.choice(np.linspace(0, 2, tie_pool),
                                  r).astype(np.float32)
            obj = rng.integers(0, 997, r).astype(np.int32)
            vali = (rng.random(r) > 0.3).astype(np.int32)
            for valid in (vali != 0, np.zeros(r, bool)):   # + all-invalid
                ref_ord = np.argsort(-np.where(valid, keys, -np.inf),
                                     kind="stable")
                want = (obj[ref_ord], keys[ref_ord], vali[ref_ord])
                got_np = policy_core.bitonic_sort_with_payload(
                    keys, (obj, keys, vali), valid=valid, xp=np)
                got_jnp = policy_core.bitonic_sort_with_payload(
                    jnp.asarray(keys),
                    (jnp.asarray(obj), jnp.asarray(keys),
                     jnp.asarray(vali)),
                    valid=jnp.asarray(valid))
                for got in (got_np, got_jnp):
                    order, skeys, pays = got
                    np.testing.assert_array_equal(
                        np.asarray(order)[:r], ref_ord, err_msg=str(r))
                    for p, w in zip(pays, want):
                        p = np.asarray(p)
                        np.testing.assert_array_equal(p[:r], w,
                                                      err_msg=str(r))
                        # pad positions carry exact zero payloads
                        np.testing.assert_array_equal(
                            p[r:], np.zeros_like(p[r:]))


def test_bitonic_apply_inverse_equals_onehot_scatter():
    """The inverse-permutation apply (ascending bitonic pass keyed on the
    DISTINCT order integers) lands value j at position order[j] — exactly
    the one-hot scatter oracle ``out[order] = values`` — for permutations
    produced by the payload sort at odd / non-pow2 sizes, duplicate keys,
    all-invalid windows; int and float payloads, both xp twins."""
    rng = np.random.default_rng(11)
    for r in (1, 3, 17, 33, 60, 128):
        keys = rng.choice(np.linspace(0, 2, 3), r).astype(np.float32)
        for valid in ((rng.random(r) > 0.3), np.zeros(r, bool)):
            order, _, _ = policy_core.bitonic_sort_with_payload(
                keys, (), valid=valid, xp=np)
            rp = order.shape[-1]
            vf = rng.uniform(-5.0, 5.0, rp).astype(np.float32)
            vi = rng.integers(0, 100, rp).astype(np.int32)
            want_f = np.empty_like(vf)
            want_i = np.empty_like(vi)
            want_f[order] = vf                    # one-hot scatter oracle
            want_i[order] = vi
            got_np = policy_core.bitonic_apply_inverse(order, (vf, vi),
                                                       xp=np)
            got_jnp = policy_core.bitonic_apply_inverse(
                jnp.asarray(order), (jnp.asarray(vf), jnp.asarray(vi)))
            for gf, gi in (got_np, got_jnp):
                np.testing.assert_array_equal(np.asarray(gf), want_f,
                                              err_msg=str(r))
                np.testing.assert_array_equal(np.asarray(gi), want_i,
                                              err_msg=str(r))


def test_rank_desc_equals_argsort_and_network():
    """The all-pairs rank (DESIGN.md §13 hot path) is the INVERSE of the
    stable argsort(-keys) permutation, and permute_to_sorted /
    permute_from_sorted reproduce take / one-hot scatter exactly — single
    non-zero term per output lane, pure relocation even for floats.
    Pinned against the argsort oracle AND the bitonic network form
    (both compute THE unique strict-total-order permutation), across odd
    and non-pow2 sizes, duplicate keys, all-invalid windows, batched 2-D
    tiles, and both xp twins."""
    rng = np.random.default_rng(13)
    for r in (1, 3, 17, 33, 60, 100, 128):
        keys = rng.choice(np.linspace(0, 2, 3), r).astype(np.float32)
        obj = rng.integers(0, 997, r).astype(np.int32)
        lat = rng.uniform(0.0, 9.0, r).astype(np.float32)
        for valid in ((rng.random(r) > 0.3), np.zeros(r, bool)):
            ref_ord = np.argsort(-np.where(valid, keys, -np.inf),
                                 kind="stable")
            for xp, as_a in ((np, np.asarray), (jnp, jnp.asarray)):
                rank, mkeys = policy_core.rank_desc(as_a(keys),
                                                    valid=as_a(valid),
                                                    xp=xp)
                # rank == inverse of the stable argsort permutation
                inv = np.empty(r, np.int64)
                inv[ref_ord] = np.arange(r)
                np.testing.assert_array_equal(np.asarray(rank), inv,
                                              err_msg=str(r))
                # gather to sorted order == take along the argsort
                obj_s, key_s = policy_core.permute_to_sorted(
                    rank, (as_a(obj), mkeys), xp=xp)
                np.testing.assert_array_equal(np.asarray(obj_s),
                                              obj[ref_ord])
                np.testing.assert_array_equal(
                    np.asarray(key_s),
                    np.where(valid, keys, -np.inf)[ref_ord])
                # network form lands the same payloads at positions < r
                _, _, (obj_net,) = policy_core.bitonic_sort_with_payload(
                    keys, (obj,), valid=valid, xp=np)
                np.testing.assert_array_equal(np.asarray(obj_s),
                                              obj_net[:r])
                # inverse apply == one-hot scatter oracle out[ord] = v
                want = np.empty_like(lat)
                want[ref_ord] = lat
                (back,) = policy_core.permute_from_sorted(
                    rank, (as_a(lat),), xp=xp)
                np.testing.assert_array_equal(np.asarray(back), want,
                                              err_msg=str(r))
    # batched 2-D tile (the kernel's (t_tile, R) shape): every stream row
    # ranks independently
    keys2 = rng.uniform(0.0, 4.0, (5, 33)).astype(np.float32)
    val2 = rng.random((5, 33)) > 0.4
    rank2, _ = policy_core.rank_desc(jnp.asarray(keys2),
                                     valid=jnp.asarray(val2))
    for i in range(5):
        ref = np.argsort(-np.where(val2[i], keys2[i], -np.inf),
                         kind="stable")
        inv = np.empty(33, np.int64)
        inv[ref] = np.arange(33)
        np.testing.assert_array_equal(np.asarray(rank2)[i], inv)


def _whole_tile_rank(keys):
    """The rank as one all-pairs ``(..., R, R)`` tile, unblocked."""
    idx = np.arange(keys.shape[-1])
    a, ia = keys[..., :, None], idx[:, None]
    b, ib = keys[..., None, :], idx[None, :]
    return np.sum((b > a) | ((b == a) & (ib < ia)), axis=-1)


def _whole_tile_apply(onehot, x, axis):
    return np.sum(np.where(onehot, x, np.zeros((), x.dtype)), axis=axis)


@pytest.mark.parametrize("r", [128, 256, 300, 384, 1008, 1024])
def test_blocked_rank_and_applies_equal_the_whole_tile(r):
    """Past one 128-lane block (DESIGN.md §19) the rank sums integer
    counts block pair by block pair and both applies fill their output
    one block at a time, so every width gives the whole tile's bits:
    the inverse of the stable argsort(-keys), the take along it and its
    one-hot scatter.  Ties on every key, an all-invalid row, widths that
    pad to whole blocks (300, 1008), both xp twins."""
    rng = np.random.default_rng(r)
    t = 3
    keys = rng.choice(np.linspace(0.0, 2.0, 4), (t, r)).astype(np.float32)
    valid = rng.random((t, r)) > 0.25
    valid[1] = False
    masked = np.where(valid, keys, -np.inf).astype(np.float32)
    order = np.argsort(-masked, axis=-1, kind="stable")
    whole = _whole_tile_rank(masked)
    np.testing.assert_array_equal(whole, np.argsort(order, axis=-1))
    onehot = whole[..., :, None] == np.arange(r)          # (t, i, p)
    obj = rng.integers(0, 8 * r, (t, r)).astype(np.int32)
    lat = rng.uniform(0.0, 9.0, (t, r)).astype(np.float32)
    sorted_obj = _whole_tile_apply(onehot, obj[..., :, None], -2)
    np.testing.assert_array_equal(sorted_obj,
                                  np.take_along_axis(obj, order, -1))
    unsorted_lat = _whole_tile_apply(onehot, lat[..., None, :], -1)
    take = rng.integers(0, r, (t, r))                     # not a permutation
    for xp, as_a in ((np, np.asarray), (jnp, jnp.asarray)):
        rank, mkeys = policy_core.rank_desc(as_a(keys), valid=as_a(valid),
                                            xp=xp)
        np.testing.assert_array_equal(np.asarray(rank), whole)
        for one_tile in (False, True):  # blocks, and the engine's tile
            obj_s, key_s = policy_core.permute_to_sorted(
                rank, (as_a(obj), mkeys), xp=xp, whole_tile=one_tile)
            np.testing.assert_array_equal(np.asarray(obj_s), sorted_obj)
            np.testing.assert_array_equal(
                np.asarray(key_s), np.take_along_axis(masked, order, -1))
            back, obj_back = policy_core.permute_from_sorted(
                rank, (as_a(lat), obj_s), xp=xp, whole_tile=one_tile)
            np.testing.assert_array_equal(np.asarray(back), unsorted_lat)
            np.testing.assert_array_equal(np.asarray(obj_back), obj)
            (taken,) = policy_core.permute_from_sorted(
                as_a(take), (as_a(lat),), xp=xp, whole_tile=one_tile)
            np.testing.assert_array_equal(np.asarray(taken),
                                          np.take_along_axis(lat, take, -1))
