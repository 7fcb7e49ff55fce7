"""Pallas kernel sweeps: shapes/dtypes vs pure-jnp oracles (interpret)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention import attention_ref, flash_attention
from repro.kernels.sched_select import sched_select, sched_select_ref

FLASH_CASES = [
    # (B, S, H, KV, hd, window, chunk, dtype)
    (2, 64, 4, 2, 32, None, None, jnp.float32),
    (1, 128, 4, 1, 64, None, None, jnp.float32),     # MQA
    (2, 96, 4, 4, 16, 32, None, jnp.float32),        # MHA + SWA
    (1, 128, 8, 2, 32, None, 32, jnp.float32),       # chunked-local
    (1, 64, 2, 2, 128, None, None, jnp.bfloat16),    # bf16 end-to-end
    (1, 80, 4, 2, 24, 24, None, jnp.float32),        # ragged S, odd hd
]


@pytest.mark.parametrize("case", FLASH_CASES)
def test_flash_matches_oracle(case):
    b, s, h, kv, hd, win, ck, dtype = case
    keys = jax.random.split(jax.random.key(hash(case) % 2**31), 3)
    q = jax.random.normal(keys[0], (b, s, h, hd), dtype)
    k = jax.random.normal(keys[1], (b, s, kv, hd), dtype)
    v = jax.random.normal(keys[2], (b, s, kv, hd), dtype)
    out = flash_attention(q, k, v, window=win, chunk=ck,
                          block_q=32, block_k=32)
    ref = attention_ref(q, k, v, window=win, chunk=ck)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    err = float(jnp.max(jnp.abs(out.astype(jnp.float32)
                                - ref.astype(jnp.float32))))
    assert err < tol, (case, err)


def test_flash_noncausal_cross():
    b, s, h, hd = 2, 64, 4, 32
    keys = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(keys[0], (b, s, h, hd))
    k = jax.random.normal(keys[1], (b, s, h, hd))
    v = jax.random.normal(keys[2], (b, s, h, hd))
    out = flash_attention(q, k, v, causal=False, block_q=32, block_k=32)
    ref = attention_ref(q, k, v, causal=False)
    assert float(jnp.max(jnp.abs(out - ref))) < 2e-5


def test_flash_block_shape_sweep():
    """Block sizes must not change the math."""
    b, s, h, kv, hd = 1, 128, 4, 2, 32
    keys = jax.random.split(jax.random.key(3), 3)
    q = jax.random.normal(keys[0], (b, s, h, hd))
    k = jax.random.normal(keys[1], (b, s, kv, hd))
    v = jax.random.normal(keys[2], (b, s, kv, hd))
    ref = attention_ref(q, k, v)
    for bq, bk in [(16, 16), (32, 64), (64, 32), (128, 128)]:
        out = flash_attention(q, k, v, block_q=bq, block_k=bk)
        assert float(jnp.max(jnp.abs(out - ref))) < 2e-5, (bq, bk)


def test_flash_is_global_flag_disables_locality():
    b, s, h, kv, hd = 1, 64, 4, 2, 32
    keys = jax.random.split(jax.random.key(4), 3)
    q = jax.random.normal(keys[0], (b, s, h, hd))
    k = jax.random.normal(keys[1], (b, s, kv, hd))
    v = jax.random.normal(keys[2], (b, s, kv, hd))
    out = flash_attention(q, k, v, window=8, is_global=True,
                          block_q=32, block_k=32)
    ref = attention_ref(q, k, v)  # plain causal
    assert float(jnp.max(jnp.abs(out - ref))) < 2e-5


SCHED_CASES = [
    # (C, N, M, policy, threshold)
    (2, 40, 16, "minload", 0.0),
    (3, 64, 100, "minload", 8.0),
    (2, 40, 16, "two_random", 0.0),
    (1, 100, 100, "two_random", 4.0),
    (4, 16, 3, "minload", 1.0),
]


@pytest.mark.parametrize("case", SCHED_CASES)
def test_sched_select_matches_oracle(case):
    c, n, m, policy, thr = case
    keys = jax.random.split(jax.random.key(hash(case) % 2**31), 3)
    objs = jax.random.randint(keys[0], (c, n), 0, 10_000, dtype=jnp.int32)
    lens = jax.random.uniform(keys[1], (c, n), minval=0.5, maxval=100.0)
    init = jax.random.uniform(keys[2], (c, m), minval=0.0, maxval=50.0)
    seeds = jnp.arange(c, dtype=jnp.uint32) * 13 + 1
    ch, fl = sched_select(objs, lens, init, seeds, n_servers=m,
                          threshold=thr, policy=policy)
    m_pad = max(-(-m // 128) * 128, 128)
    for i in range(c):
        ip = jnp.pad(init[i], (0, m_pad - m))
        rch, rfl = sched_select_ref(objs[i], lens[i], ip, seeds[i],
                                    n_servers=m, threshold=thr, lam=32.0,
                                    policy=policy)
        np.testing.assert_array_equal(np.asarray(ch[i]), np.asarray(rch))
        np.testing.assert_allclose(np.asarray(fl[i]), np.asarray(rfl[:m]),
                                   atol=1e-3)


def test_sched_select_avoids_straggler():
    c, n, m = 2, 60, 12
    objs = jax.random.randint(jax.random.key(0), (c, n), 0, 999,
                              dtype=jnp.int32)
    lens = jnp.ones((c, n)) * 4.0
    init = jnp.zeros((c, m)).at[:, 5].set(1e5)  # server 5 = straggler
    ch, _ = sched_select(objs, lens, init,
                         jnp.asarray([1, 2], jnp.uint32), n_servers=m,
                         threshold=1.0, policy="minload")
    assert int((np.asarray(ch) == 5).sum()) == 0


def test_sched_select_conserves_bytes():
    c, n, m = 1, 30, 8
    objs = jnp.arange(n, dtype=jnp.int32)[None]
    lens = jnp.ones((1, n)) * 2.5
    init = jnp.zeros((1, m))
    ch, fl = sched_select(objs, lens, init, jnp.asarray([9], jnp.uint32),
                          n_servers=m, policy="two_random")
    assert float(fl.sum()) == pytest.approx(n * 2.5, rel=1e-5)


# ---------------------------------------------------------------------------
# Temporal stream kernel: kernel == ref == engine (bit-exact, interpret)
# ---------------------------------------------------------------------------

from repro.core import engine, statlog  # noqa: E402
from repro.core.engine import ClusterTrace, Workload  # noqa: E402
from repro.core.policies import PolicyConfig  # noqa: E402
from repro.core.statlog import LogConfig  # noqa: E402
from repro.kernels.sched_select import sched_stream, sched_stream_ref  # noqa: E402


def _transient_trace(m, base=200.0, slow_ids=(3, 5), factor=8.0,
                     onset=0.05, recover=0.15):
    slow = np.full(m, base, np.float32)
    slow[list(slow_ids)] = base / factor
    return ClusterTrace(
        times=jnp.asarray([0.0, onset, recover], jnp.float32),
        rates=jnp.asarray(np.stack([np.full(m, base, np.float32), slow,
                                    np.full(m, base, np.float32)])))


def _stream_case(m, r, seed=0):
    rng = np.random.default_rng(seed)
    return Workload(jnp.asarray(rng.integers(0, 8 * m, r), jnp.int32),
                    jnp.asarray(rng.uniform(1.0, 20.0, r), jnp.float32),
                    jnp.ones((r,), bool))


STREAM_CASES = [
    # (M, R, window, policy, threshold) — M deliberately NOT 128-aligned;
    # R=250/window=60 exercises a padded (partially invalid) last window.
    (100, 240, 60, "ect", 0.05),
    (100, 240, 60, "trh", 4.0),
    (37, 250, 60, "ect", 0.05),
    (37, 250, 60, "trh", 4.0),
    (130, 120, 40, "ect", 0.05),
    (3, 64, 16, "trh", 0.0),
    # sort-based policies (DESIGN.md §10): in-VMEM bitonic request sort
    # (mlml) + recursive-average sections (nltr); odd M, padded windows
    (37, 250, 60, "mlml", 4.0),
    (37, 250, 60, "nltr", 4.0),
    (100, 240, 60, "nltr", 4.0),
    (130, 120, 40, "mlml", 4.0),
    # baselines through the kernel: no-guard rr, probing two_choice
    (24, 130, 40, "rr", 0.0),
    (24, 130, 40, "two_choice", 2.0),
]

_LCG_POLICIES = ("trh", "nltr", "two_choice")


@pytest.mark.parametrize("case", STREAM_CASES)
def test_stream_kernel_engine_parity_transient(case):
    """Every kernel policy runs in-kernel with per-window drain and
    matches the JAX engine BIT-EXACTLY over a transient-straggler trace
    (grouped steps, completion feedback, per-window renorm — the whole
    temporal path).  Randomized policies replay the kernel's LCG via
    PolicyConfig(rng='lcg')."""
    m, r, win, policy, thr = case
    trace = _transient_trace(m, slow_ids=(min(3, m - 1),))
    cfg = LogConfig(n_servers=m, lam=50.0)
    state = statlog.init_state(cfg, rates=trace.rates[0])
    work = _stream_case(m, r, seed=hash(case) % 2**31)
    pol = PolicyConfig(name=policy, threshold=thr,
                       rng="lcg" if policy in _LCG_POLICIES else "jax")
    a = engine.run_stream(state, work, jax.random.key(2), policy=pol,
                          log_cfg=cfg, window_size=win, trace=trace,
                          window_dt=0.04, backend="jax")
    b = engine.run_stream(state, work, jax.random.key(2), policy=pol,
                          log_cfg=cfg, window_size=win, trace=trace,
                          window_dt=0.04, backend="kernel")
    for f in ("chosen", "latencies", "redirected", "window_loads"):
        np.testing.assert_array_equal(np.asarray(getattr(a, f)),
                                      np.asarray(getattr(b, f)), err_msg=f)
    np.testing.assert_array_equal(np.asarray(a.state.log),
                                  np.asarray(b.state.log))
    np.testing.assert_array_equal(np.asarray(a.state.n_assigned),
                                  np.asarray(b.state.n_assigned))


@pytest.mark.parametrize("policy", ["ect", "trh", "minload", "two_random",
                                    "mlml", "nltr", "rr", "two_choice"])
def test_stream_kernel_matches_ref_oracle(policy):
    """Kernel == scan oracle on the packed table, padded windows, odd M."""
    m, n_win, win = 37, 4, 32
    rng = np.random.default_rng(7)
    n = n_win * win
    obj = jnp.asarray(rng.integers(0, 500, n), jnp.int32)
    lens = jnp.asarray(rng.uniform(1, 8, n), jnp.float32)
    valid = jnp.asarray(rng.random(n) > 0.2)
    rates = jnp.asarray(rng.uniform(50, 300, (n_win, m)), jnp.float32)
    state = statlog.init_state(LogConfig(n_servers=m, lam=20.0))
    seed = jnp.uint32(12345)
    kw = dict(n_servers=m, window_size=win, threshold=2.0, lam=20.0,
              window_dt=0.01, policy=policy, observe=True, renorm=True)
    ch, lat, tab, wl = sched_stream(obj, lens, valid, state.log, seed,
                                    rates, **kw)
    rch, rlat, rtab, rwl = sched_stream_ref(obj, lens, valid, state.log,
                                            seed, rates, **kw)
    np.testing.assert_array_equal(np.asarray(ch), np.asarray(rch))
    np.testing.assert_array_equal(np.asarray(lat), np.asarray(rlat))
    np.testing.assert_array_equal(np.asarray(tab), np.asarray(rtab))
    np.testing.assert_array_equal(np.asarray(wl), np.asarray(rwl))


def test_stream_kernel_degenerate_static_matches_legacy_minload():
    """With a degenerate static setup (one window, unit rates, no drain,
    no feedback, no renorm) the stream kernel reproduces the legacy
    static kernel bit-for-bit."""
    c, n, m = 2, 50, 24
    keys = jax.random.split(jax.random.key(5), 3)
    objs = jax.random.randint(keys[0], (c, n), 0, 999, dtype=jnp.int32)
    lens = jax.random.uniform(keys[1], (c, n), minval=1.0, maxval=30.0)
    init = jax.random.uniform(keys[2], (c, m), maxval=50.0)
    seeds = jnp.arange(c, dtype=jnp.uint32) * 7 + 3
    for policy in ("minload", "two_random"):
        ch_old, fl_old = sched_select(objs, lens, init, seeds, n_servers=m,
                                      threshold=2.0, policy=policy)
        for i in range(c):
            table = jnp.stack([init[i], jnp.full((m,), 1.0 / m),
                               jnp.zeros((m,)), jnp.ones((m,))])
            ch, _, tab, _ = sched_stream(
                objs[i], lens[i], jnp.ones((n,), bool), table, seeds[i],
                jnp.ones((1, m), jnp.float32), n_servers=m, window_size=n,
                threshold=2.0, lam=32.0, window_dt=0.0, policy=policy,
                observe=False, renorm=False)
            np.testing.assert_array_equal(np.asarray(ch_old[i]),
                                          np.asarray(ch))
            np.testing.assert_allclose(np.asarray(fl_old[i]),
                                       np.asarray(tab[0]), atol=1e-3)


# ---------------------------------------------------------------------------
# Trial-grid batch kernel: the whole sweep as ONE pallas_call (DESIGN.md §9)
# ---------------------------------------------------------------------------

from repro.core import policy_core  # noqa: E402
from repro.kernels.sched_select import (sched_stream_batch,  # noqa: E402
                                        sched_stream_batch_ref)


def _batch_case(t, m, n_win, win, seed=0):
    rng = np.random.default_rng(seed)
    n = n_win * win
    return (jnp.asarray(rng.integers(0, 8 * m, (t, n)), jnp.int32),
            jnp.asarray(rng.uniform(1.0, 20.0, (t, n)), jnp.float32),
            jnp.asarray(rng.random((t, n)) > 0.2),      # padded windows
            jnp.stack([statlog.init_state(LogConfig(n_servers=m,
                                                    lam=50.0)).log] * t),
            jnp.asarray(rng.integers(0, 2**31, (t,)), jnp.uint32),
            jnp.asarray(rng.uniform(50.0, 300.0, (t, n_win, m)), jnp.float32))


BATCH_CASES = [
    # (T, M, W, win, tile, policy) — odd M, T not a multiple of the grid
    # tile (inert padded trials), partially-invalid (padded) windows.
    (5, 37, 4, 32, 2, "ect"),
    (5, 37, 4, 32, 2, "trh"),
    (10, 100, 5, 40, 8, "ect"),      # headline shape, T padded 10 -> 16
    (16, 130, 4, 50, 8, "trh"),      # M wider than one 128-lane tile
    (3, 24, 4, 30, 3, "ect"),
    # M_pad = 384 is NOT a power of two: lane_sum's in-kernel renorm
    # reduction must pad 384 -> 512 (the only path that exercises it)
    (4, 300, 3, 32, 4, "trh"),
    # sort-based policies on the trial grid (DESIGN.md §10): per-window
    # bitonic sorts vectorized over trial sublanes; T % tile != 0
    (5, 37, 4, 32, 2, "mlml"),
    (5, 37, 4, 32, 2, "nltr"),
    (6, 24, 4, 30, 4, "nltr"),
    (3, 24, 3, 30, 3, "rr"),
    (3, 24, 3, 30, 3, "two_choice"),
    # tile None: the row-depth default (DESIGN.md §20), one program of 20
    # trials, against trial_tile=8 (three programs, 4 inert trials)
    (20, 100, 3, 100, None, "ect"),
    (20, 100, 3, 100, None, "rr"),
    (20, 100, 2, 100, None, "mlml"),
]


@pytest.mark.parametrize("case", enumerate(BATCH_CASES),
                         ids=lambda c: str(c[1]) if isinstance(c, tuple)
                         else None)
def test_stream_batch_matches_ref_and_sequential(case):
    """Trial-grid kernel == batched oracle == per-trial sequential kernel:
    choices, latencies, loads, window loads and fused metrics BIT-EXACT
    (the tentpole contract); probability/EWMA-derived table rows to float
    tolerance — `jnp.exp`'s polynomial may contract differently at some
    tile widths (DESIGN.md §9), a drift the decision outputs never see."""
    # stable per-case seed (hash() varies with PYTHONHASHSEED — a failing
    # bit-exactness case must reproduce across processes)
    idx, (t, m, n_win, win, tile, policy) = case
    obj, lens, valid, tables, seeds, rates = _batch_case(
        t, m, n_win, win, seed=1000 + idx)
    kw = dict(n_servers=m, window_size=win, threshold=2.0, lam=50.0,
              window_dt=0.02, policy=policy, observe=True, renorm=True)
    ch, lat, tab, wl, met = sched_stream_batch(obj, lens, valid, tables,
                                               seeds, rates,
                                               trial_tile=tile, **kw)
    rch, rlat, rtab, rwl, rmet = sched_stream_batch_ref(
        obj, lens, valid, tables, seeds, rates, **kw)
    np.testing.assert_array_equal(np.asarray(ch), np.asarray(rch))
    np.testing.assert_array_equal(np.asarray(lat), np.asarray(rlat))
    np.testing.assert_array_equal(np.asarray(wl), np.asarray(rwl))
    np.testing.assert_array_equal(np.asarray(met), np.asarray(rmet))
    np.testing.assert_array_equal(
        np.asarray(tab[:, policy_core.ROW_LOADS]),
        np.asarray(rtab[:, policy_core.ROW_LOADS]))
    np.testing.assert_allclose(np.asarray(tab), np.asarray(rtab), atol=1e-6)
    if tile is None:
        # trials are independent sublanes: the default tile's outputs,
        # final tables whole, are the 8-deep tile's bit for bit
        deep = policy_core.resolve_trial_tile(
            t, n_servers=m, n_requests=obj.shape[1], window_size=win,
            policy=policy)
        assert deep > 8
        outs8 = sched_stream_batch(obj, lens, valid, tables, seeds, rates,
                                   trial_tile=8, **kw)
        for a, b in zip((ch, lat, tab, wl, met), outs8):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # per-trial sequential kernel (the lax.map path's unit of work)
    for i in range(t):
        c1, l1, _, w1 = sched_stream(obj[i], lens[i], valid[i], tables[i],
                                     seeds[i], rates[i], **kw)
        np.testing.assert_array_equal(np.asarray(ch[i]), np.asarray(c1))
        np.testing.assert_array_equal(np.asarray(lat[i]), np.asarray(l1))
        np.testing.assert_array_equal(np.asarray(wl[i]), np.asarray(w1))


def test_stream_batch_tile1_full_table_exact():
    """At trial_tile=1 the grid form degenerates to the PR-2 single-stream
    kernel: the ENTIRE final table is bit-exact vs the oracle."""
    obj, lens, valid, tables, seeds, rates = _batch_case(3, 37, 4, 32,
                                                         seed=11)
    kw = dict(n_servers=37, window_size=32, threshold=2.0, lam=50.0,
              window_dt=0.02, policy="ect", observe=True, renorm=True)
    outs = sched_stream_batch(obj, lens, valid, tables, seeds, rates,
                              trial_tile=1, **kw)
    refs = sched_stream_batch_ref(obj, lens, valid, tables, seeds, rates,
                                  **kw)
    for a, b in zip(outs, refs):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_stream_batch_fused_metrics_definition():
    """The fused metrics row equals the canonical host-side definitions:
    makespan = max window-open + latency over valid steps; p99 = the
    nearest-rank (ceil(0.99 n)-th order) statistic; sum/max/count over
    the valid latencies."""
    t, m, n_win, win = 6, 24, 5, 30
    obj, lens, valid, tables, seeds, rates = _batch_case(t, m, n_win, win,
                                                         seed=5)
    dt = 0.03
    kw = dict(n_servers=m, window_size=win, threshold=2.0, lam=50.0,
              window_dt=dt, policy="ect", observe=True, renorm=True)
    _, lat, _, _, met = sched_stream_batch(obj, lens, valid, tables, seeds,
                                           rates, trial_tile=3, **kw)
    lat, met, vnp = np.asarray(lat), np.asarray(met), np.asarray(valid)
    for i in range(t):
        vl = lat[i][vnp[i]]
        w_open = ((np.arange(n_win * win) // win).astype(np.float32)
                  * np.float32(dt))
        mk = float((w_open[vnp[i]] + vl).max())
        k = int(np.ceil(0.99 * len(vl)))
        p99 = float(np.sort(vl)[k - 1])
        assert met[i, policy_core.MET_MAKESPAN] == pytest.approx(mk, abs=0)
        assert met[i, policy_core.MET_P99] == pytest.approx(
            p99, rel=1e-6), (met[i, policy_core.MET_P99], p99)
        assert met[i, policy_core.MET_LAT_SUM] == pytest.approx(
            float(vl.sum()), rel=1e-5)
        assert met[i, policy_core.MET_LAT_MAX] == float(vl.max())
        assert met[i, policy_core.MET_N_VALID] == float(len(vl))


def test_run_stream_batch_engine_parity():
    """engine.run_stream_batch == lax.map of run_stream(backend='kernel')
    == the vmapped jax engine, over a transient trace — decisions,
    latencies, loads, redirects and probe accounting bit-exact."""
    t, m, r, win = 5, 37, 250, 60
    trace = _transient_trace(m, slow_ids=(3,))
    cfg = LogConfig(n_servers=m, lam=50.0)
    keys = jax.random.split(jax.random.key(7), t)
    rng = np.random.default_rng(9)
    works = Workload(
        jnp.asarray(rng.integers(0, 8 * m, (t, r)), jnp.int32),
        jnp.asarray(rng.uniform(1.0, 20.0, (t, r)), jnp.float32),
        jnp.ones((t, r), bool))
    state = statlog.init_state(cfg, rates=trace.rates[0])
    states = jax.tree.map(lambda a: jnp.broadcast_to(a, (t,) + a.shape),
                          state)
    traces = jax.tree.map(lambda a: jnp.broadcast_to(a, (t,) + a.shape),
                          trace)
    for policy, rng_mode in (("ect", "jax"), ("trh", "lcg")):
        pol = PolicyConfig(name=policy, threshold=0.05, rng=rng_mode)
        batch, metrics, _ = engine.run_stream_batch(
            states, works, keys, policy=pol, log_cfg=cfg, window_size=win,
            traces=traces, window_dt=0.04, observe=True)

        def one(w_k, backend):
            w, k = w_k
            return engine.run_stream(state, w, k, policy=pol, log_cfg=cfg,
                                     window_size=win, trace=trace,
                                     window_dt=0.04, observe=True,
                                     backend=backend)
        seq = jax.lax.map(lambda wk: one(wk, "kernel"), (works, keys))
        eng = jax.vmap(lambda w, k: one((w, k), "jax"))(works, keys)
        for other in (seq, eng):
            for f in ("chosen", "latencies", "redirected", "window_loads"):
                np.testing.assert_array_equal(
                    np.asarray(getattr(batch, f)),
                    np.asarray(getattr(other, f)), err_msg=(policy, f))
            np.testing.assert_array_equal(
                np.asarray(batch.state.n_assigned),
                np.asarray(other.state.n_assigned))
        np.testing.assert_array_equal(np.asarray(batch.probe_msgs),
                                      np.asarray(seq.probe_msgs))
        # fused makespan == the canonical reduction over the seq path
        w_open = (jnp.arange(r) // win).astype(jnp.float32) * 0.04
        np.testing.assert_array_equal(
            np.asarray(metrics[:, policy_core.MET_MAKESPAN]),
            np.asarray(jnp.max(w_open[None] + seq.latencies, axis=-1)))


@pytest.mark.parametrize("policy", ["mlml", "nltr", "trh"])
def test_blocked_window_plan_engine_parity(policy):
    """300 servers and windows of 260 requests: the window plan's ranks
    and applies run in three 128-lane blocks on both axes (DESIGN.md
    §19).  The batched kernel == the vmapped jax engine, bit for bit,
    over a transient trace."""
    t, m, r, win = 8, 300, 520, 260
    trace = _transient_trace(m, slow_ids=(3, 150, 299))
    cfg = LogConfig(n_servers=m, lam=50.0)
    keys = jax.random.split(jax.random.key(15), t)
    rng = np.random.default_rng(15)
    works = Workload(
        jnp.asarray(rng.integers(0, 8 * m, (t, r)), jnp.int32),
        jnp.asarray(rng.uniform(1.0, 20.0, (t, r)), jnp.float32),
        jnp.ones((t, r), bool))
    state = statlog.init_state(cfg, rates=trace.rates[0])
    states = jax.tree.map(lambda a: jnp.broadcast_to(a, (t,) + a.shape),
                          state)
    traces = jax.tree.map(lambda a: jnp.broadcast_to(a, (t,) + a.shape),
                          trace)
    pol = PolicyConfig(name=policy, threshold=4.0, rng="lcg")
    batch, _, _ = engine.run_stream_batch(
        states, works, keys, policy=pol, log_cfg=cfg, window_size=win,
        traces=traces, window_dt=0.04, observe=True)
    eng = jax.vmap(lambda w, k: engine.run_stream(
        state, w, k, policy=pol, log_cfg=cfg, window_size=win, trace=trace,
        window_dt=0.04, observe=True, backend="jax"))(works, keys)
    for f in ("chosen", "latencies", "redirected", "window_loads"):
        np.testing.assert_array_equal(np.asarray(getattr(batch, f)),
                                      np.asarray(getattr(eng, f)),
                                      err_msg=f)
    np.testing.assert_array_equal(np.asarray(batch.state.n_assigned),
                                  np.asarray(eng.state.n_assigned))
    np.testing.assert_array_equal(
        np.asarray(batch.state.log[:, policy_core.ROW_LOADS]),
        np.asarray(eng.state.log[:, policy_core.ROW_LOADS]))


@pytest.mark.parametrize("policy", ["mlml", "nltr"])
def test_stream_batch_sort_policy_all_invalid_final_window(policy):
    """A FULLY padded (all-invalid) final window: every sort key in the
    window is -inf, nvalid = 0 collapses the nLTR section bounds to 0,
    and the LCG still advances on the dead steps — kernel == batched
    oracle == engine, bit-exact (DESIGN.md §10 edge case)."""
    t, m, n_win, win = 4, 37, 4, 30
    obj, lens, valid, tables, seeds, rates = _batch_case(t, m, n_win, win,
                                                         seed=21)
    valid = valid.at[:, -win:].set(False)        # kill the last window
    kw = dict(n_servers=m, window_size=win, threshold=2.0, lam=50.0,
              window_dt=0.02, policy=policy, observe=True, renorm=True)
    outs = sched_stream_batch(obj, lens, valid, tables, seeds, rates,
                              trial_tile=2, **kw)
    refs = sched_stream_batch_ref(obj, lens, valid, tables, seeds, rates,
                                  **kw)
    for name, a, b in zip(("ch", "lat", "tab", "wl", "met"), outs, refs):
        if name == "tab":
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-6, err_msg=name)
            np.testing.assert_array_equal(
                np.asarray(a[:, policy_core.ROW_LOADS]),
                np.asarray(b[:, policy_core.ROW_LOADS]), err_msg=name)
        else:
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                          err_msg=name)
    # dead-window latencies are exactly zero (masked writes)
    np.testing.assert_array_equal(np.asarray(outs[1][:, -win:]), 0.0)


# ---------------------------------------------------------------------------
# 2-D (trials × clients) grid kernel: per_client contention on the kernel
# path with in-VMEM cross-client merge (DESIGN.md §11)
# ---------------------------------------------------------------------------

from repro.kernels.sched_select import (sched_stream_grid,  # noqa: E402
                                        sched_stream_grid_ref)


def _grid_case(t, c, m, n_win, win, seed=0, dead_clients=()):
    """(T, C, N) streams; ``dead_clients`` marks whole client slices
    invalid in every trial (phantom clients)."""
    rng = np.random.default_rng(seed)
    n = n_win * win
    valid = rng.random((t, c, n)) > 0.2
    for dc in dead_clients:
        valid[:, dc, :] = False
    return (jnp.asarray(rng.integers(0, 8 * m, (t, c, n)), jnp.int32),
            jnp.asarray(rng.uniform(1.0, 20.0, (t, c, n)), jnp.float32),
            jnp.asarray(valid),
            jnp.broadcast_to(statlog.init_state(
                LogConfig(n_servers=m, lam=50.0)).log, (t, c, 4, m)),
            jnp.asarray(rng.integers(0, 2**31, (t, c)), jnp.uint32),
            jnp.asarray(rng.uniform(50.0, 300.0, (t, n_win, m)), jnp.float32))


GRID_CASES = [
    # (T, C, M, W, win, t_tile, c_tile, policy, dead_clients) — odd M,
    # T % t_tile != 0 and C % c_tile != 0 (inert trial AND phantom client
    # padding), multi-block client merges, whole dead client slices.
    (3, 5, 37, 3, 20, 2, 2, "ect", ()),
    (2, 3, 24, 2, 16, 8, 8, "trh", ()),          # tiles wider than T, C
    (2, 4, 25, 2, 16, 1, 4, "nltr", ()),
    (3, 2, 24, 2, 10, 2, 1, "mlml", ()),         # c_tile=1: per-client blocks
    (2, 5, 17, 2, 12, 2, 2, "two_choice", (1,)),  # dead client mid-row
    (2, 3, 24, 2, 10, 2, 3, "rr", (0, 2)),        # mostly-dead trials
]


@pytest.mark.parametrize("case", enumerate(GRID_CASES),
                         ids=lambda c: str(c[1]) if isinstance(c, tuple)
                         else None)
def test_stream_grid_matches_ref_and_sequential(case):
    """2-D grid kernel == vmap² oracle == per-stream sequential kernel:
    choices, latencies, loads, window loads, per-stream metrics AND the
    in-VMEM cross-client merges (masked client-mean window loads,
    merged metric row) BIT-EXACT — the §11 tentpole contract.  Same
    float-tolerance carve-out for the probability/EWMA table rows as
    the 1-D grid (DESIGN.md §9).  Stable per-case seed — hash() varies
    with PYTHONHASHSEED, and a failing bit-exactness case must
    reproduce across processes."""
    idx, (t, c, m, n_win, win, tt, ct, policy, dead) = case
    obj, lens, valid, tables, seeds, rates = _grid_case(
        t, c, m, n_win, win, seed=2000 + idx, dead_clients=dead)
    kw = dict(n_servers=m, window_size=win, threshold=2.0, lam=50.0,
              window_dt=0.02, policy=policy, observe=True, renorm=True)
    outs = sched_stream_grid(obj, lens, valid, tables, seeds, rates,
                             trial_tile=tt, client_tile=ct, **kw)
    refs = sched_stream_grid_ref(obj, lens, valid, tables, seeds, rates,
                                 client_tile=ct, **kw)
    names = ("choices", "lats", "tables", "wloads", "metrics",
             "cm_wloads", "cm_metrics", "cm_lats", "cm_lval")
    for name, a, b in zip(names, outs, refs):
        a, b = np.asarray(a), np.asarray(b)
        if name == "tables":
            np.testing.assert_array_equal(a[:, :, policy_core.ROW_LOADS],
                                          b[:, :, policy_core.ROW_LOADS],
                                          err_msg=name)
            np.testing.assert_allclose(a, b, atol=1e-6, err_msg=name)
        else:
            np.testing.assert_array_equal(a, b, err_msg=name)
    # per-stream == the sequential single-stream kernel (all of a
    # trial's clients share its rate trace)
    for i in range(t):
        for j in range(c):
            c1, l1, _, w1 = sched_stream(obj[i, j], lens[i, j], valid[i, j],
                                         tables[i, j], seeds[i, j],
                                         rates[i], **kw)
            np.testing.assert_array_equal(np.asarray(outs[0][i, j]),
                                          np.asarray(c1))
            np.testing.assert_array_equal(np.asarray(outs[1][i, j]),
                                          np.asarray(l1))
            np.testing.assert_array_equal(np.asarray(outs[3][i, j]),
                                          np.asarray(w1))


def test_stream_grid_client_merge_masks_phantoms():
    """The in-VMEM cross-client merge weights REAL clients only: with
    dead (all-invalid) client slices, cm_metrics' client count excludes
    them and cm_wloads equals the policy_core twins computed from the
    surviving per-stream outputs — including across client-tile block
    boundaries (C=5 over c_tile=2 -> 3 blocks with phantom padding).
    The merged latency block (DESIGN.md §14) masks dead clients' rows
    to exact zeros with zero validity, and MET_P99 equals the host
    `nearest_rank_p99` bisection over that block."""
    t, c, m, n_win, win = 2, 5, 24, 2, 12
    obj, lens, valid, tables, seeds, rates = _grid_case(
        t, c, m, n_win, win, seed=77, dead_clients=(0, 3))
    kw = dict(n_servers=m, window_size=win, threshold=2.0, lam=50.0,
              window_dt=0.02, policy="ect", observe=True, renorm=True)
    (_, lats, _, wloads, metrics, cm_wl, cm_met, cm_lats, cm_lval) = \
        sched_stream_grid(obj, lens, valid, tables, seeds, rates,
                          trial_tile=2, client_tile=2, **kw)
    cvalid = jnp.any(valid, axis=-1)
    np.testing.assert_array_equal(
        np.asarray(cm_met[:, policy_core.MET_N_CLIENTS]),
        np.asarray(jnp.sum(cvalid.astype(jnp.float32), axis=-1)))
    assert (np.asarray(cm_met[:, policy_core.MET_N_CLIENTS]) == 3.0).all()
    ref_wl = jax.vmap(
        lambda w, v: policy_core.masked_client_mean(w, v, 2))(wloads, cvalid)
    np.testing.assert_array_equal(np.asarray(cm_wl), np.asarray(ref_wl))
    ref_met = jax.vmap(
        lambda mm, v, ml, mv: policy_core.client_stream_metrics(
            mm, v, 2, merged_lats=ml, merged_valid=mv))(
        metrics, cvalid, cm_lats, valid)
    np.testing.assert_array_equal(np.asarray(cm_met), np.asarray(ref_met))
    # merged latency block: valid slots carry the per-stream latencies
    # verbatim, dead clients / invalid slots are exact zeros
    np.testing.assert_array_equal(
        np.asarray(cm_lats), np.asarray(jnp.where(valid, lats, 0.0)))
    np.testing.assert_array_equal(
        np.asarray(cm_lval), np.asarray(jnp.where(valid, 1.0, 0.0)))
    np.testing.assert_array_equal(np.asarray(cm_lats[:, 0]), 0.0)
    np.testing.assert_array_equal(np.asarray(cm_lval[:, 3]), 0.0)
    # MET_P99 == the host value-bisection over the merged block (order-
    # insensitive, so the (C, N) layout is immaterial)
    host_p99 = policy_core.nearest_rank_p99(
        cm_lats.reshape(t, -1), cm_lval.reshape(t, -1) != 0.0)[:, 0]
    np.testing.assert_array_equal(
        np.asarray(cm_met[:, policy_core.MET_P99]), np.asarray(host_p99))
    # dead clients' latencies are exactly zero (masked writes)
    np.testing.assert_array_equal(np.asarray(lats[:, 0]), 0.0)


def test_stream_grid_merged_p99_oracle_edge_cases():
    """Merged-p99 edge cases (DESIGN.md §14): an ALL-INVALID trial
    (every client dead) pins MET_P99 to exactly 0, and C > R (more
    clients than per-client requests) keeps kernel == vmap² oracle ==
    host bisection bit-exact."""
    # all clients dead in every trial -> nvalid = 0 -> p99 = 0 exactly
    t, c, m, n_win, win = 2, 3, 24, 2, 10
    obj, lens, valid, tables, seeds, rates = _grid_case(
        t, c, m, n_win, win, seed=91, dead_clients=(0, 1, 2))
    kw = dict(n_servers=m, window_size=win, threshold=2.0, lam=50.0,
              window_dt=0.02, policy="ect", observe=True, renorm=True)
    outs = sched_stream_grid(obj, lens, valid, tables, seeds, rates,
                             trial_tile=2, client_tile=2, **kw)
    np.testing.assert_array_equal(
        np.asarray(outs[6][:, policy_core.MET_P99]), 0.0)
    np.testing.assert_array_equal(np.asarray(outs[7]), 0.0)
    np.testing.assert_array_equal(np.asarray(outs[8]), 0.0)
    # C > R: 7 clients of single-window 4-request streams
    t, c, m, n_win, win = 2, 7, 17, 1, 4
    obj, lens, valid, tables, seeds, rates = _grid_case(
        t, c, m, n_win, win, seed=92, dead_clients=(2,))
    kw = dict(n_servers=m, window_size=win, threshold=2.0, lam=50.0,
              window_dt=0.02, policy="trh", observe=True, renorm=True)
    outs = sched_stream_grid(obj, lens, valid, tables, seeds, rates,
                             trial_tile=2, client_tile=3, **kw)
    refs = sched_stream_grid_ref(obj, lens, valid, tables, seeds, rates,
                                 client_tile=3, **kw)
    for name, a, b in zip(("choices", "lats", "tables", "wloads",
                           "metrics", "cm_wloads", "cm_metrics",
                           "cm_lats", "cm_lval"), outs, refs):
        if name == "tables":
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-6, err_msg=name)
        else:
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                          err_msg=name)
    host_p99 = policy_core.nearest_rank_p99(
        outs[7].reshape(t, -1), outs[8].reshape(t, -1) != 0.0)[:, 0]
    np.testing.assert_array_equal(
        np.asarray(outs[6][:, policy_core.MET_P99]), np.asarray(host_p99))


def test_run_stream_batch_2d_engine_parity():
    """engine.run_stream_batch with a (T, C) leading batch == the vmap²
    jax engine per stream, and its ClientMerge equals the policy_core
    twins — the engine-layer contract the simulator's per_client kernel
    dispatch rides on (trace shared per trial)."""
    t, c, m, r, win = 2, 3, 25, 48, 16
    trace = _transient_trace(m, slow_ids=(3,))
    cfg = LogConfig(n_servers=m, lam=50.0)
    rng = np.random.default_rng(13)
    works = Workload(
        jnp.asarray(rng.integers(0, 8 * m, (t, c, r)), jnp.int32),
        jnp.asarray(rng.uniform(1.0, 20.0, (t, c, r)), jnp.float32),
        jnp.asarray(rng.random((t, c, r)) > 0.1))
    state = statlog.init_state(cfg, rates=trace.rates[0])
    states = jax.tree.map(
        lambda a: jnp.broadcast_to(a, (t, c) + a.shape), state)
    traces = jax.tree.map(lambda a: jnp.broadcast_to(a, (t,) + a.shape),
                          trace)
    keys = jax.random.split(jax.random.key(5), t * c).reshape(t, c)
    pol = PolicyConfig(name="trh", threshold=0.05, rng="lcg")
    batch, metrics, merged = engine.run_stream_batch(
        states, works, keys, policy=pol, log_cfg=cfg, window_size=win,
        traces=traces, window_dt=0.04, observe=True, client_tile=2)
    assert metrics.shape == (t, c, policy_core.N_METRICS)

    def one(st, w, k):
        return engine.run_stream(st, w, k, policy=pol, log_cfg=cfg,
                                 window_size=win, trace=trace,
                                 window_dt=0.04, observe=True,
                                 backend="jax")
    eng = jax.vmap(jax.vmap(one))(states, works, keys)
    for f in ("chosen", "latencies", "redirected", "window_loads"):
        np.testing.assert_array_equal(np.asarray(getattr(batch, f)),
                                      np.asarray(getattr(eng, f)),
                                      err_msg=f)
    np.testing.assert_array_equal(np.asarray(batch.state.n_assigned),
                                  np.asarray(eng.state.n_assigned))
    cvalid = jnp.any(works.valid, axis=-1)
    np.testing.assert_array_equal(
        np.asarray(merged.window_loads_mean),
        np.asarray(jax.vmap(
            lambda w, v: policy_core.masked_client_mean(w, v, 2))(
            batch.window_loads, cvalid)))


def test_mlml_kernel_pairs_longest_with_lightest():
    """Behavioural: with a uniform prior, MLML through the kernel pairs
    the longest request of the window with the lightest (lowest-index)
    server — Alg. 1's circular positional pairing, same as the engine."""
    m, win = 8, 8
    lens = jnp.asarray([3.0, 9.0, 1.0, 7.0, 5.0, 2.0, 8.0, 4.0],
                       jnp.float32)
    obj = jnp.arange(win, dtype=jnp.int32)
    table = statlog.init_state(LogConfig(n_servers=m, lam=1e9)).log
    ch, _, _, _ = sched_stream(
        obj, lens, jnp.ones((win,), bool), table, jnp.uint32(0),
        jnp.ones((1, m), jnp.float32), n_servers=m, window_size=win,
        threshold=-1e9, lam=1e9, window_dt=0.0, policy="mlml",
        observe=False, renorm=False)
    # uniform probs -> sorted_servers = [0..M); k-th longest -> server k
    order = np.argsort(-np.asarray(lens), kind="stable")
    expect = np.empty(win, np.int32)
    expect[order] = np.arange(win)
    np.testing.assert_array_equal(np.asarray(ch), expect)


def test_stream_kernel_avoids_transient_straggler():
    """Behavioural check: during the slow phase of a transient trace, ECT
    (kernel backend) steers work away from the straggler."""
    m, r, win = 24, 360, 60
    trace = _transient_trace(m, slow_ids=(5,), onset=0.02, recover=0.5,
                             factor=16.0)
    cfg = LogConfig(n_servers=m, lam=50.0)
    state = statlog.init_state(cfg, rates=trace.rates[0])
    work = _stream_case(m, r, seed=11)
    res = engine.run_stream(state, work, jax.random.key(0),
                            policy=PolicyConfig(name="ect", threshold=0.05),
                            log_cfg=cfg, window_size=win, trace=trace,
                            window_dt=0.1, backend="kernel")
    chosen = np.asarray(res.chosen)
    # slow phase covers windows 1..2 (onset 2% .. recovery 50% of 0.6s)
    mid = chosen[win:3 * win]
    frac_mid = float((mid == 5).sum()) / len(mid)
    assert frac_mid < 1.0 / m, frac_mid  # well under the uniform share
