"""Plain reference of the paper's Section 4 sweep: inputs and decisions.

Imports nothing of the program under test.  Two parts:

* ``trial_inputs`` — the benchmark's own copy of the Section 4
  generators: for a trial key, the request stream (object ids, lengths in
  three size classes), the initial server loads (Normal) and the
  transient straggler schedule.  It derives every draw from the key in the
  order the program documents, so a change to the program's sampler shows
  as wrong answers, not as a faster sweep.
* ``simulate`` — the scheduler itself, written straight from the paper
  (Eqs. (1)-(3), the ECT and MLML policies found by name under
  ``policies/``, the per-window queue drain), one stream per vmap lane.
  With ``forced`` it is a teacher-forced replay: it decides at every step
  what the policy would choose, records that, and then books the server
  the program chose, so one disagreement never spreads to later steps.
  Without ``forced`` it runs free, and in ``dtype=bfloat16`` it is the
  benchmark's control: the same reference in the next precision down.

Semantics, per stream:

* the stream is cut into windows of ``window`` requests; requests of one
  window on the same object form one step (the first occurrence carries
  the summed length; duplicates share its decision and latency);
* the policy sees the log: loads, selection probabilities, EWMA of
  observed MB/s and estimated rates; at window start the true service
  rates are those of the trace at ``w * window_dt``;
* after each step: Eq. (1) load, Eq. (2)/(3) probabilities, latency =
  load of the chosen server / its true rate, and the observed MB/s of the
  request folds into the EWMA (observation is on for every non-static
  scenario);
* after each window: probabilities renormalised, queues drained for
  ``window_dt`` seconds at the true rates.
"""

from __future__ import annotations

import dataclasses
import functools
import importlib
import os
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


@dataclasses.dataclass(frozen=True)
class Shape:
    """Static sizes of one sweep, from a configuration and a traffic mix."""

    n_servers: int
    n_requests: int
    n_trials: int
    window_size: int
    n_clients: int
    per_client: bool
    workload: str
    init_load_mean: float
    init_load_std: float
    straggler_frac: float
    straggler_factor: float
    small_lo: float
    small_hi: float
    medium_hi: float
    large_hi: float
    scenario: str
    base_rate: float
    slow_factor: float
    scn_straggler_frac: float
    window_dt_opt: Optional[float]
    onset: float
    recover: float
    policy: str
    threshold: float
    lam: float
    alpha: float = 0.25

    @property
    def mean_request_mb(self) -> float:
        classes = {
            "small": (self.small_lo + self.small_hi) / 2,
            "medium": (self.small_hi + self.medium_hi) / 2,
            "large": (self.medium_hi + self.large_hi) / 2,
        }
        if self.workload == "mixed":
            return sum(classes.values()) / 3
        return classes[self.workload]

    @property
    def window_dt(self) -> float:
        if self.window_dt_opt is not None:
            return float(self.window_dt_opt)
        if self.scenario == "static":
            return 0.0
        return (self.window_size * self.mean_request_mb
                / (self.n_servers * self.base_rate))

    @property
    def n_windows(self) -> int:
        return -(-self.n_requests // self.window_size)

    @property
    def streams(self) -> tuple:
        """(clients per trial, requests per client stream, window)."""
        if not self.per_client:
            return 1, self.n_requests, self.window_size
        per = -(-self.n_requests // self.n_clients)
        return self.n_clients, per, min(self.window_size, per)


def shape_from(config: dict, traffic: dict) -> Shape:
    """The reference's sizes from the two data files of a cell."""
    s, scn, pol = config["sim"], traffic["scenario"], traffic["policy"]
    mean = Shape(**_shape_kw(s, scn, pol, lam=1.0)).mean_request_mb
    expected_load = s["n_requests"] * mean / s["n_servers"]
    lam = max(4.0 * mean, expected_load)
    return Shape(**_shape_kw(s, scn, pol, lam=lam))


def _shape_kw(s, scn, pol, lam):
    return dict(
        n_servers=s["n_servers"], n_requests=s["n_requests"],
        n_trials=s["n_trials"], window_size=s["window_size"],
        n_clients=s["n_clients"],
        per_client=s["client_model"] == "per_client",
        workload=s["workload"], init_load_mean=s["init_load_mean"],
        init_load_std=s["init_load_std"],
        straggler_frac=s["straggler_frac"],
        straggler_factor=s["straggler_factor"], small_lo=s["small_lo"],
        small_hi=s["small_hi"], medium_hi=s["medium_hi"],
        large_hi=s["large_hi"], scenario=scn["name"],
        base_rate=scn["base_rate_mb_s"], slow_factor=scn["slow_factor"],
        scn_straggler_frac=scn["straggler_frac"],
        window_dt_opt=scn["window_dt"], onset=scn["onset"],
        recover=scn["recover"], policy=pol["name"],
        threshold=pol["threshold"], lam=lam)


# ---------------------------------------------------------------------------
# Section 4 generators
# ---------------------------------------------------------------------------


def trial_inputs(key, sh: Shape) -> dict:
    """One trial's inputs from its key.

    The key splits three ways (loads, stream, scheduler); the loads key
    splits into the Normal noise and the load-straggler pick; the stream
    key into object ids, size class, and the three class sizes; the
    trace's straggler set comes from ``fold_in(key, 0x7e3)``."""
    m, r = sh.n_servers, sh.n_requests
    k_load, k_work, _ = jax.random.split(key, 3)
    k_norm, k_strag = jax.random.split(k_load)
    noise = sh.init_load_std * jax.random.normal(k_norm, (m,))
    init = jnp.maximum(sh.init_load_mean + noise, 0.0)
    mask = jnp.zeros((m,), bool)
    n_load_strag = int(round(sh.straggler_frac * m))
    if n_load_strag > 0:
        idx = jax.random.choice(k_strag, m, (n_load_strag,), replace=False)
        mask = mask.at[idx].set(True)
        extra = sh.straggler_factor * (r * sh.mean_request_mb / m)
        init = init + mask * extra
    k_obj, k_cls, k_small, k_med, k_large = jax.random.split(k_work, 5)
    obj = jax.random.randint(k_obj, (r,), 0, 8 * m, dtype=jnp.int32)
    small = jax.random.uniform(k_small, (r,), minval=sh.small_lo,
                               maxval=sh.small_hi)
    med = jax.random.uniform(k_med, (r,), minval=sh.small_hi,
                             maxval=sh.medium_hi)
    large = jax.random.uniform(k_large, (r,), minval=sh.medium_hi,
                               maxval=sh.large_hi)
    lengths = {"small": small, "medium": med, "large": large}.get(sh.workload)
    if lengths is None:
        cls = jax.random.randint(k_cls, (r,), 0, 3)
        lengths = jnp.where(cls == 0, small, jnp.where(cls == 1, med, large))
    times, rates, slow = _trace(jax.random.fold_in(key, 0x7e3), sh)
    return dict(init=init.astype(jnp.float32), obj=obj,
                lengths=lengths.astype(jnp.float32), times=times,
                rates=rates, straggler_mask=mask | slow)


def _trace(key, sh: Shape):
    """Rate schedule: (times (E,), rates (E, M), slow-at-any-time mask)."""
    m, base = sh.n_servers, sh.base_rate
    base_row = jnp.full((m,), base, jnp.float32)
    if sh.scenario == "static":
        return (jnp.zeros((1,), jnp.float32), base_row[None],
                jnp.zeros((m,), bool))
    if sh.scenario != "transient":
        raise ValueError(f"the reference models the static and transient "
                         f"scenarios, not {sh.scenario!r}")
    horizon = max(sh.n_windows * sh.window_dt, 1e-6)
    n = max(int(round(sh.scn_straggler_frac * m)), 1)
    idx = jax.random.choice(key, m, (n,), replace=False)
    slow = jnp.zeros((m,), bool).at[idx].set(True)
    slow_row = jnp.where(slow, base / sh.slow_factor, base).astype(jnp.float32)
    times = jnp.asarray([0.0, sh.onset * horizon, sh.recover * horizon],
                        jnp.float32)
    return times, jnp.stack([base_row, slow_row, base_row]), slow


def sweep_inputs(key, sh: Shape) -> dict:
    """Every trial's inputs: the sweep key splits into one key per trial."""
    keys = jax.random.split(key, sh.n_trials)
    return jax.vmap(lambda k: trial_inputs(k, sh))(keys)


# ---------------------------------------------------------------------------
# The scheduler
# ---------------------------------------------------------------------------


def load_policy(name: str):
    """The decision rule of a policy, from ``policies/<name>.py``."""
    if not os.path.exists(os.path.join(HERE, "policies", name + ".py")):
        raise ValueError(f"the reference has no policy {name!r} "
                         f"(no policies/{name}.py)")
    return importlib.import_module(f"bench.policies.{name}")


def _window_steps(obj, lengths, valid, rank_key):
    """Steps of one window: which requests open a step, each request's
    step (the index of its first occurrence), the step's summed length,
    and the processing order of the opening requests."""
    w = obj.shape[0]
    idx = jnp.arange(w)
    same = (obj[:, None] == obj[None, :]) & valid[:, None] & valid[None, :]
    first_of = jnp.argmax(same, axis=1)          # earliest same-object row
    first_of = jnp.where(valid, first_of, idx)
    opens = valid & (first_of == idx)
    step_len = jnp.sum(jnp.where(same, lengths[None, :], 0.0), axis=1)
    order = rank_key(obj, step_len, opens)
    return first_of, opens, step_len, order


def _stream(sh: Shape, policy, window: int, obj, lengths, valid, init,
            times, rates_tab, forced, dtype):
    """One stream (a trial, or one client of a trial) through the log."""
    m = sh.n_servers
    n = obj.shape[0]
    n_win = -(-n // window)
    pad = n_win * window - n
    if pad:
        obj = jnp.concatenate([obj, jnp.zeros((pad,), obj.dtype)])
        lengths = jnp.concatenate([lengths, jnp.zeros((pad,), lengths.dtype)])
        valid = jnp.concatenate([valid, jnp.zeros((pad,), bool)])
        if forced is not None:
            forced = jnp.concatenate([forced, jnp.zeros((pad,), jnp.int32)])
    obj_w = obj.reshape(n_win, window)
    len_w = lengths.reshape(n_win, window).astype(dtype)
    val_w = valid.reshape(n_win, window)
    replay = forced is not None
    forced_w = (forced if replay else jnp.zeros_like(obj)).reshape(n_win,
                                                                    window)
    dt = sh.window_dt
    t_open = jnp.arange(n_win, dtype=jnp.float32) * jnp.float32(dt)
    ev = jnp.sum(times[None, :] <= t_open[:, None], axis=1) - 1
    win_rates = rates_tab[jnp.clip(ev, 0, times.shape[0] - 1)].astype(dtype)
    observe = sh.scenario != "static"

    loads = init.astype(dtype)
    p = jnp.exp(-loads / dtype(sh.lam)) / dtype(m)
    probs = p / jnp.sum(p)
    log = dict(loads=loads, probs=probs, ewma=jnp.zeros((m,), dtype),
               est=jnp.ones((m,), dtype))
    lane = jnp.arange(m)

    def window_fn(log, xs):
        o, ln, v, rates, fw = xs
        first_of, opens, step_len, order = _window_steps(
            o, ln, v, policy.rank_key)
        plan = policy.plan(log, m)

        def step(log, pos):
            j = order[pos]
            live = opens[j]
            oj, lj = o[j], step_len[j]
            default = (oj % m).astype(jnp.int32)
            mine = policy.choose(log, plan, pos, default, lj,
                                 dtype(sh.threshold), m)
            c = fw[j] if replay else mine
            hit = lane == c
            loads = jnp.where(hit, log["loads"] + lj, log["loads"])
            l_c, p_c = loads[c], log["probs"][c]
            e = jnp.exp(-l_c / dtype(sh.lam))
            probs = jnp.where(hit, p_c * e,
                              log["probs"] + p_c * (1 - e) / dtype(m - 1))
            lat = l_c / jnp.maximum(rates[c], dtype(1e-6))
            new = dict(loads=loads, probs=probs, ewma=log["ewma"],
                       est=log["est"])
            if observe:
                mbs = lj / jnp.maximum(lat, dtype(1e-9))
                old = log["ewma"][c]
                upd = jnp.where(old == 0, mbs,
                                (1 - dtype(sh.alpha)) * old
                                + dtype(sh.alpha) * mbs)
                ewma = jnp.where(hit, upd, log["ewma"])
                best = jnp.maximum(jnp.max(ewma), dtype(1.0))
                new["ewma"] = ewma
                new["est"] = jnp.where(ewma > 0, ewma, best)
            log = jax.tree.map(lambda a, b: jnp.where(live, b, a), log, new)
            return log, (j, mine, c, lat)

        log, (js, mine, used, lat) = jax.lax.scan(step, log,
                                                  jnp.arange(window))
        back = jnp.zeros((window,), jnp.int32).at[js].set(jnp.arange(window))
        mine, used, lat = mine[back], used[back], lat[back]
        # every request takes its step's decision and latency
        mine_r, used_r, lat_r = mine[first_of], used[first_of], lat[first_of]
        pr = jnp.clip(log["probs"], 0)
        log = dict(log, probs=pr / jnp.sum(pr))
        if dt:
            dec = jnp.maximum(jnp.maximum(rates, dtype(1e-6)) * dtype(dt), 0)
            log = dict(log, loads=jnp.maximum(log["loads"] - dec, 0))
        return log, (mine_r, used_r, jnp.where(v, lat_r, 0), log["loads"])

    _, (mine, used, lat, wl) = jax.lax.scan(
        window_fn, log, (obj_w, len_w, val_w, win_rates, forced_w))
    w_open = jnp.maximum(t_open, 0)[:, None]
    done = jnp.where(val_w, w_open + lat.astype(jnp.float32), 0)
    return dict(mine=mine.reshape(-1)[:n], used=used.reshape(-1)[:n],
                lat=lat.reshape(-1)[:n].astype(jnp.float32),
                window_loads=wl.astype(jnp.float32),
                makespan=jnp.max(done))


def simulate(inputs: dict, sh: Shape, forced=None, dtype=jnp.float32):
    """The whole sweep: per-trial results in the program's field names,
    plus ``mine``, the reference's own decision for every request.

    ``forced`` (T, R) int32: the program's ``chosen``, replayed."""
    policy = load_policy(sh.policy)
    c, per, window = sh.streams
    t, r, m = sh.n_trials, sh.n_requests, sh.n_servers
    one = lambda o, ln, v, init, times, rates, f: _stream(  # noqa: E731
        sh, policy, window, o, ln, v, init, times, rates, f, dtype)
    obj, lengths = inputs["obj"], inputs["lengths"]
    valid = jnp.ones((t, r), bool)
    if sh.per_client:
        pad = c * per - r

        def split(a, fill):
            a = jnp.concatenate(
                [a, jnp.full((t, pad), fill, a.dtype)], axis=1) if pad else a
            return a.reshape(t, c, per)

        f = None if forced is None else split(forced, 0)
        args = (split(obj, 0), split(lengths, 0), split(valid, False))
        inner = jax.vmap(one, in_axes=(0, 0, 0, None, None, None,
                                       None if f is None else 0))
        out = jax.vmap(inner, in_axes=(0, 0, 0, 0, 0, 0,
                                       None if f is None else 0))(
            *args, inputs["init"], inputs["times"], inputs["rates"], f)
        real = jnp.any(args[2], axis=-1)                      # (T, C)
        n_real = jnp.maximum(jnp.sum(real, axis=1), 1)
        wl = jnp.sum(jnp.where(real[:, :, None, None], out["window_loads"],
                               0), axis=1) / n_real[:, None, None]
        makespan = jnp.max(jnp.where(real, out["makespan"], 0), axis=1)
        flat = lambda a: a.reshape(t, c * per)[:, :r]  # noqa: E731
        out = dict(mine=flat(out["mine"]), used=flat(out["used"]),
                   lat=flat(out["lat"]), window_loads=wl, makespan=makespan)
    else:
        out = jax.vmap(one, in_axes=(0, 0, 0, 0, 0, 0,
                                     None if forced is None else 0))(
            obj, lengths, valid, inputs["init"], inputs["times"],
            inputs["rates"], forced)
    chosen = out["used"]
    onehot = chosen[:, :, None] == jnp.arange(m)
    written = jnp.sum(jnp.where(onehot, lengths[:, :, None], 0), axis=1)
    mask = inputs["straggler_mask"]
    return dict(
        mine=out["mine"], chosen=chosen,
        server_loads=inputs["init"] + written,
        n_assigned=jnp.sum(onehot, axis=1).astype(jnp.int32),
        probe_msgs=jnp.zeros((t,), jnp.int32),
        straggler_hits=jnp.sum(jnp.take_along_axis(mask, chosen, axis=1),
                               axis=1).astype(jnp.int32),
        redirected=jnp.sum(chosen != obj % m, axis=1).astype(jnp.int32),
        init_loads=inputs["init"], straggler_mask=mask,
        latencies=out["lat"], phase_time=out["makespan"],
        window_loads=out["window_loads"],
        window_size_eff=jnp.full((t,), window, jnp.int32))


@functools.lru_cache(maxsize=None)
def reference_fn(sh: Shape, forced: bool, dtype=jnp.float32):
    """Jitted ``key (, chosen) -> reference outputs`` for one sweep."""
    if forced:
        return jax.jit(lambda key, chosen: simulate(
            sweep_inputs(key, sh), sh, forced=chosen, dtype=dtype))
    return jax.jit(lambda key: simulate(sweep_inputs(key, sh), sh,
                                        dtype=dtype))


def to_numpy(tree) -> dict:
    return {k: np.asarray(v) for k, v in tree.items()}
