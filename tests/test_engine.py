"""Window/step engine: grouping, windowing, padding invariance."""

import collections
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from bench import stagetrace
from repro.core import engine, policy_core, simulate, statlog
from repro.core.engine import Workload
from repro.core.policies import PolicyConfig
from repro.core.statlog import LogConfig


def test_group_by_object_aggregates_lengths():
    work = Workload(jnp.asarray([3, 1, 3, 2, 1], jnp.int32),
                    jnp.asarray([1.0, 2.0, 4.0, 8.0, 16.0]),
                    jnp.ones((5,), bool))
    g = engine.group_by_object(work)
    got = {int(o): float(l) for o, l, v in
           zip(g.object_ids, g.lengths, g.valid) if bool(v)}
    assert got == {1: 18.0, 2: 8.0, 3: 5.0}
    assert int(g.valid.sum()) == 3


def test_group_by_object_respects_padding():
    work = Workload(jnp.asarray([5, 5, 7], jnp.int32),
                    jnp.asarray([1.0, 1.0, 9.0]),
                    jnp.asarray([True, False, True]))
    g = engine.group_by_object(work)
    got = {int(o): float(l) for o, l, v in
           zip(g.object_ids, g.lengths, g.valid) if bool(v)}
    assert got == {5: 1.0, 7: 9.0}


@given(n=st.integers(1, 40), w=st.integers(1, 17))
def test_stream_padding_invariance(n, w):
    """Total scheduled bytes are independent of window size."""
    rng = np.random.default_rng(0)
    obj = jnp.asarray(rng.integers(0, 50, n), jnp.int32)
    lens = jnp.asarray(rng.uniform(1, 10, n), jnp.float32)
    cfg = LogConfig(n_servers=7, lam=32.0)
    work = Workload(obj, lens, jnp.ones((n,), bool))
    res = engine.run_stream(statlog.init_state(cfg), work,
                            jax.random.key(1),
                            policy=PolicyConfig(name="rr"), log_cfg=cfg,
                            window_size=w)
    assert res.chosen.shape == (n,)
    # RR must equal object mod M regardless of windowing
    np.testing.assert_array_equal(np.asarray(res.chosen),
                                  np.asarray(obj) % 7)


def test_stream_load_accounting_matches_chosen():
    rng = np.random.default_rng(3)
    n, m = 64, 10
    obj = jnp.asarray(rng.integers(0, 200, n), jnp.int32)
    lens = jnp.asarray(rng.uniform(1, 5, n), jnp.float32)
    cfg = LogConfig(n_servers=m, lam=64.0)
    work = Workload(obj, lens, jnp.ones((n,), bool))
    res = engine.run_stream(statlog.init_state(cfg), work,
                            jax.random.key(0),
                            policy=PolicyConfig(name="trh", threshold=0.5),
                            log_cfg=cfg, window_size=16, group_steps=False)
    per_server = np.zeros(m)
    for s, l in zip(np.asarray(res.chosen), np.asarray(lens)):
        per_server[s] += l
    np.testing.assert_allclose(np.asarray(res.state.loads), per_server,
                               rtol=1e-4)


def test_jit_cache_stable():
    """run_stream_jit compiles once per static config."""
    cfg = LogConfig(n_servers=4, lam=32.0)
    pol = PolicyConfig(name="trh", threshold=1.0)
    work = Workload(jnp.arange(8, dtype=jnp.int32),
                    jnp.ones((8,), jnp.float32), jnp.ones((8,), bool))
    r1 = engine.run_stream_jit(statlog.init_state(cfg), work,
                               jax.random.key(0), policy=pol, log_cfg=cfg,
                               window_size=4)
    r2 = engine.run_stream_jit(statlog.init_state(cfg), work,
                               jax.random.key(0), policy=pol, log_cfg=cfg,
                               window_size=4)
    np.testing.assert_array_equal(np.asarray(r1.chosen),
                                  np.asarray(r2.chosen))


# --- the step grouping's window-local dense form (DESIGN.md §18) --------

INT32_MAX = np.iinfo(np.int32).max


def _group_sort_scatter(work):
    """The step grouping as it was before DESIGN.md §18, kept as the
    oracle: backend stable argsort, gathers, segment sums and the
    inverse-permutation scatter, for one (R,) window."""
    r = work.n_requests
    ids = jnp.where(work.valid, work.object_ids, INT32_MAX)
    order = jnp.argsort(ids, stable=True)
    s_ids = ids[order]
    s_len = work.lengths[order] * work.valid[order]
    is_first = jnp.concatenate([jnp.ones((1,), bool), s_ids[1:] != s_ids[:-1]])
    seg = jnp.cumsum(is_first) - 1
    summed = jax.ops.segment_sum(s_len, seg, num_segments=r)
    agg_len = jnp.where(is_first, summed[seg], 0.0)
    agg_valid = is_first & (s_ids != INT32_MAX)
    grouped = Workload(
        object_ids=jnp.where(agg_valid, s_ids, 0).astype(jnp.int32),
        lengths=agg_len.astype(jnp.float32),
        valid=agg_valid)
    rows = jnp.arange(r, dtype=jnp.int32)
    seg_first = jax.ops.segment_min(rows, seg, num_segments=r)
    inv_order = jnp.zeros((r,), jnp.int32).at[order].set(rows)
    return grouped, seg_first[seg[inv_order]]


def _windows(seed, shape, mode):
    """Request windows of ``shape`` (..., w) for one grouping case."""
    rng = np.random.default_rng(seed)
    w = shape[-1]
    lens = rng.uniform(0.25, 1024.0, shape).astype(np.float32)
    valid = rng.random(shape) > 0.2
    if mode == "near_max":
        ids = INT32_MAX - rng.integers(0, 4, shape)
    elif mode == "all_same":
        ids = np.full(shape, rng.integers(0, 800))
        valid = np.ones(shape, bool)
    else:
        # about as many distinct ids as the window: pairs, triples, more
        ids = rng.integers(0, max(w, 2), shape)
    if mode == "all_invalid":
        valid = np.zeros(shape, bool)
    return Workload(jnp.asarray(ids.astype(np.int32)), jnp.asarray(lens),
                    jnp.asarray(valid))


def _blocked_sum(terms):
    """A step's length as pinned: its requests' lengths in request
    order, the first power-of-two block summed (recursively) plus the
    rest (recursively), in float32."""
    if len(terms) == 1:
        return np.float32(terms[0])
    p = 1 << (len(terms) - 1).bit_length() - 1
    return np.float32(_blocked_sum(terms[:p]) + _blocked_sum(terms[p:]))


def _step_len_oracle(work):
    """Each request's step length by :func:`_blocked_sum`, per window."""
    ids = np.where(np.asarray(work.valid), np.asarray(work.object_ids),
                   INT32_MAX)
    lens = np.asarray(work.lengths) * np.asarray(work.valid)
    out = np.zeros(ids.shape, np.float32)
    for idx in np.ndindex(ids.shape):
        win = idx[:-1]
        out[idx] = _blocked_sum(lens[win][ids[win] == ids[idx]])
    return ids, out


_new_grouping = jax.jit(engine.group_by_object_with_map)
_old_grouping = {n: jax.jit(functools.reduce(lambda f, _: jax.vmap(f),
                                             range(n), _group_sort_scatter))
                 for n in (1, 2, 3)}


@pytest.mark.parametrize("w", [1, 10, 100, 128])
@pytest.mark.parametrize("batch", [(3,), (2, 2), (2, 2, 2)],
                         ids=["W", "T-W", "T-C-W"])
@settings(max_examples=8)
@given(seed=st.integers(0, 2 ** 32 - 1),
       mode=st.sampled_from(["random", "all_invalid", "all_same",
                             "near_max"]))
def test_step_grouping_matches_sort_and_scatter(batch, w, seed, mode):
    """The dense grouping gives the sort-and-scatter grouping's layout
    bit for bit: ids, validity and the request -> step map everywhere,
    lengths wherever a step holds one or two requests (exact under any
    association), and the pinned blocked sum for longer steps."""
    work = _windows(seed, batch + (w,), mode)
    got, got_map = _new_grouping(work)
    want, want_map = _old_grouping[len(batch)](work)
    np.testing.assert_array_equal(np.asarray(got.object_ids),
                                  np.asarray(want.object_ids))
    np.testing.assert_array_equal(np.asarray(got.valid),
                                  np.asarray(want.valid))
    np.testing.assert_array_equal(np.asarray(got_map), np.asarray(want_map))
    assert got.lengths.dtype == want.lengths.dtype == jnp.float32
    assert got_map.dtype == want_map.dtype
    # every request's step row, in grouped order: its step's size there
    ids, pinned = _step_len_oracle(work)
    step_map = np.asarray(want_map)
    size = (ids[..., :, None] == ids[..., None, :]).sum(-1)
    small = np.zeros(ids.shape, bool)
    pinned_at_step = np.zeros(ids.shape, np.float32)
    np.put_along_axis(small, step_map, size <= 2, axis=-1)
    np.put_along_axis(pinned_at_step, step_map, pinned, axis=-1)
    g_len, w_len = np.asarray(got.lengths), np.asarray(want.lengths)
    np.testing.assert_array_equal(g_len[small], w_len[small])
    first = np.zeros(ids.shape, bool)
    np.put_along_axis(first, step_map, True, axis=-1)
    np.testing.assert_array_equal(g_len[first], pinned_at_step[first])
    np.testing.assert_array_equal(g_len[~first], 0.0)


def test_bookkeeping_relocation_matches_take():
    """`_kernel_bookkeeping` sends each step's decision back to its
    requests with one-hot relocations: the same values as a take by
    ``req_to_step``, bit for bit."""
    rng = np.random.default_rng(7)
    n_win, w, m = 4, 10, 16
    work = _windows(3, (n_win, w), "random")
    grouped, req_to_step = engine.group_by_object_with_map(work)
    choices = jnp.asarray(rng.integers(0, m, n_win * w).astype(np.int32))
    lats = jnp.asarray(rng.uniform(0.0, 9.0, n_win * w).astype(np.float32))
    state = statlog.init_state(LogConfig(n_servers=m, lam=32.0))
    res = engine._kernel_bookkeeping(
        state, choices, lats, state.log, jnp.zeros((n_win, m)),
        grouped.object_ids, grouped.valid, work.valid, req_to_step,
        state.rates, policy=PolicyConfig(name="ect"), window_dt=0.0,
        n_win=n_win, window_size=w, r=n_win * w)
    take = jax.vmap(lambda a, idx: a[idx])
    ch = choices.reshape(n_win, w)
    redir = (ch != grouped.object_ids % m) & grouped.valid
    np.testing.assert_array_equal(
        np.asarray(res.chosen), np.asarray(take(ch, req_to_step)).ravel())
    np.testing.assert_array_equal(
        np.asarray(res.latencies),
        np.asarray(take(lats.reshape(n_win, w), req_to_step)
                   * work.valid).ravel())
    np.testing.assert_array_equal(
        np.asarray(res.redirected),
        np.asarray(take(redir, req_to_step) & work.valid).ravel())


_HLO_INSTR = re.compile(r"^\s*(?:ROOT )?%([\w.\-]+) = .*?\s([a-z][a-z\-]*)\(")


@pytest.mark.parametrize("window", [100, 10])
@pytest.mark.parametrize("client_model", ["shared_log", "per_client"])
def test_step_grouping_and_bookkeeping_hold_no_gather(client_model, window):
    """The compiled sweep keeps no gather or scatter under the step
    grouping's scope ``sched/engine_prep`` and no gather under
    ``sched/book`` (DESIGN.md §18); the book's integer per-server count
    may stay a scatter.  Every instruction of every computation is read
    with the scope path of its ``op_name``, as the stage trace reads it."""
    cfg = simulate.SimConfig(
        n_servers=16, n_requests=200, n_trials=2, window_size=window,
        n_clients=200 // window, client_model=client_model,
        backend="kernel", straggler_frac=0.1,
        scenario=simulate.ScenarioConfig(name="transient"))
    pol = PolicyConfig(name="ect", threshold=0.05)
    text = simulate.run_trials.lower(
        jax.random.key(0), cfg, pol,
        simulate.default_log_cfg(cfg)).compile().as_text()
    _, paths = stagetrace.program_op_names(text)
    ops = collections.Counter()
    for line in text.splitlines():
        m = _HLO_INSTR.match(line)
        if m:
            stage = stagetrace.stage_of(paths.get(m.group(1), ""))
            ops[stage, m.group(2)] += 1
    stages = {s for s, _ in ops}
    assert {"engine_prep", "book"} <= stages
    assert not ops["engine_prep", "gather"] + ops["engine_prep", "scatter"]
    assert not ops["book", "gather"]
