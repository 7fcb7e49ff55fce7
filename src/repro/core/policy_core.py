"""Single source of truth for the scheduler's decision math.

Every layer of the stack — the jitted JAX engine (`core/engine.py` via
`core/statlog.py` / `core/policies.py`), the numpy host twin on the real
I/O request path (`HostStatLog` / `HostScheduler`, used by `io/client`),
and the Pallas kernel (`kernels/sched_select`) — schedules against the
same packed **log tensor**:

    row 0  ``loads``      expected outstanding MB per server (Eq. 1)
    row 1  ``probs``      selection probability, sums to 1 (Eqs. 2-3)
    row 2  ``ewma_lat``   EWMA of *observed* service rate, MB/s (0 = unseen)
    row 3  ``est_rates``  client-estimated service rate — derived ONLY from
                          completion observations (``ect_rates`` of row 2),
                          never from the cluster's true rates.  Stale by
                          construction: when a server's true rate changes,
                          this row lags until completions reveal it.

one ``(4, M)`` float table (`N_ROWS` x servers).  ``SchedState.log``
stores it as a jnp array, ``HostStatLog.table`` as a numpy array whose
rows are views, and the kernel pins it in a ``(4, M_pad)`` VMEM scratch
for an entire request stream.

The functions here are the *decision core*: target selection scores, the
paper's redirect-threshold guard, the Eq. (1)-(3) log updates, completion
observation, per-window renormalization and queue drain.  They are
parameterized over the array namespace (``xp = jnp`` or ``numpy``) so the
JAX engine and the host twin execute literally the same code; the kernel
mirrors the same formulas with one-hot lane writes (no scatter) and is
held bit-exact by the parity tests in ``tests/test_kernels.py``.

True rates (`SchedState.rates` / `HostStatLog.rates`) are deliberately
NOT part of the table: they belong to the cluster, not the client's log.
Only :func:`drain_loads` (queue drain between windows — the simulator's
ground-truth step) and latency *reporting* consume them.  Scheduling
decisions (ECT scores, threshold guards) read ``est_rates``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

# Packed log-tensor rows (DESIGN.md §8).
ROW_LOADS, ROW_PROBS, ROW_EWMA, ROW_EST = 0, 1, 2, 3
N_ROWS = 4
ROW_NAMES = ("loads", "probs", "ewma_lat", "est_rates")

# Fused per-trial stream metrics (DESIGN.md §9): reduced in-VMEM by the
# trial-grid kernel while the latency block is still resident, so the
# headline sweep metrics never round-trip through HBM.  Lane layout of
# the kernel's (T, MET_PAD) metrics output; `stream_metrics` below is
# the bit-exact host/engine twin.
MET_MAKESPAN, MET_P99, MET_LAT_SUM, MET_LAT_MAX, MET_N_VALID = 0, 1, 2, 3, 4
N_METRICS = 5
MET_NAMES = ("makespan", "p99_lat", "lat_sum", "lat_max", "n_valid")
MET_PAD = 128          # kernel metrics row padded to one f32 lane tile

# Cross-client merged metrics (DESIGN.md §11/§14/§17): the 2-D (trials ×
# clients) grid's per-stream metric rows are merged into one per-TRIAL
# row — lanes [0, N_METRICS) keep the MET_* meaning merged over REAL
# clients (makespan/lat_max by max, lat_sum/n_valid through
# `masked_client_sum`; the p99 lane is the nearest-rank p99 of the
# MERGED latency block of all the trial's clients — `nearest_rank_p99`
# is layout- and order-insensitive, so merging needs no association
# contract), plus the real-client count.  `client_stream_metrics` below
# computes it for both backends.
MET_N_CLIENTS = 5
N_CMETRICS = 6
CMET_NAMES = MET_NAMES + ("n_clients",)

# Clients per program-instance block in the 2-D grid (DESIGN.md §11).
# Like the trial tile it keeps stream-sublane counts at multiples of the
# native f32 sublane count; it is ALSO an association parameter — the
# cross-client float merges sum client blocks of this width (see
# `masked_client_sum`) — so the jax path resolves it through
# `resolve_client_tile` too, even when no kernel runs.  32 (up from 8,
# DESIGN.md §14): per_client blocks stay small because the per-client
# slice shrinks as the client count grows (tt·ct·per ≈ tt·R floats once
# C ≥ ct), and a deeper tile quarters the grid's program count — at the
# 64-client short-stream instance that measured 1.4× end-to-end under
# interpret, where per-program dispatch dominates.
DEFAULT_CLIENT_TILE = 32


def resolve_client_tile(n_clients: int, client_tile=None) -> int:
    """Effective clients-per-block of the 2-D grid AND of the cross-client
    merge association (both layers must resolve it identically)."""
    ct = DEFAULT_CLIENT_TILE if client_tile is None else client_tile
    return max(min(ct, n_clients), 1)


# trials per program instance in the trial-grid form: the sublane count
# of the native f32 (8, 128) TPU tile, so each vectorized table op fills
# whole tiles instead of one sublane in eight.  Also the floor of the
# shape rule below.
DEFAULT_TRIAL_TILE = 8

# The trial-grid tile by row depth (DESIGN.md §20).  Each serial step of
# the kernel is a chain of dependent cross-lane reductions over
# (s_tile, M_pad) rows: at one vreg a row the chain's latency leaves the
# vector units idle, so a deeper tile runs more trials for nearly the
# same step time, until a row of ROW_VREG_BUDGET vregs fills them.
ROW_VREG_BUDGET = 8
SUBLANES, LANES = 8, 128    # one f32 vreg; LANES also aligns a window
# The window plan's live all-pairs tiles, each (s_tile, 128, 128) f32
# (policies with a plan: `PLAN_POLICIES`), and the scoped VMEM the kernel
# compiles with (Mosaic's default; the kernel sets no vmem_limit_bytes).
PLAN_POLICIES = ("trh", "mlml", "nltr")
PLAN_TILES = 5
SCOPED_VMEM_BYTES = 16 << 20


def _whole(n: int, unit: int) -> int:
    return -(-n // unit) * unit


def trial_grid_vmem_bytes(s_tile: int, n_servers: int, window_size: int,
                          n_windows: int, plan: bool) -> int:
    """VMEM one trial-grid program of ``s_tile`` streams asks for, in
    bytes: the double-buffered request, choice and latency blocks, the
    table, rate, decrement, window-load, seed and metrics blocks, the
    table scratch and, with a window plan, its ``(s_tile, 128, 128)``
    tiles.  Words of 4 bytes, in (8, 128) tiles."""
    m_pad = _whole(n_servers, LANES)
    buffered = sum((5 * n_windows * _whole(window_size, LANES),
                    2 * _whole(N_ROWS, SUBLANES) * m_pad,
                    3 * _whole(n_windows, SUBLANES) * m_pad,
                    2 * LANES))
    per_stream = sum((2 * buffered, N_ROWS * m_pad,
                      PLAN_TILES * LANES * LANES if plan else 0))
    return 4 * _whole(s_tile, SUBLANES) * per_stream


def resolve_trial_tile(n_trials: int, trial_tile=None, *, n_servers=None,
                       n_requests=None, window_size=None, policy=None,
                       n_devices: int = 1) -> int:
    """Effective trials-per-block of the trial grid, for the trials one
    device runs (``ceil(n_trials / n_devices)``).  The tile is a
    lowering parameter (XLA specializes the block shape to it), so the
    kernel dispatch, the sharded sweep and the engine must all resolve
    it through here (DESIGN.md §12).

    An explicit ``trial_tile`` wins, clamped to the trials.  Unset, the
    tile is ``DEFAULT_TRIAL_TILE`` unless the shapes are given
    (``n_servers``, ``n_requests`` per stream, ``window_size`` and
    ``policy``; the trial grid's dispatch gives them): then it is the
    deepest multiple of ``SUBLANES`` whose ``(s_tile, M_pad)`` row stays
    within ``ROW_VREG_BUDGET`` vregs and whose program stays within
    ``SCOPED_VMEM_BYTES`` (`trial_grid_vmem_bytes`), never shallower than
    the default, balanced over the programs it takes (DESIGN.md §20)."""
    per = max(-(-n_trials // n_devices), 1)
    if trial_tile is not None or n_servers is None:
        tt = DEFAULT_TRIAL_TILE if trial_tile is None else trial_tile
        return max(min(tt, per), 1)
    n_windows = -(-n_requests // window_size)
    plan = policy in PLAN_POLICIES
    deepest = max(ROW_VREG_BUDGET * LANES // _whole(n_servers, LANES)
                  * SUBLANES, DEFAULT_TRIAL_TILE)
    while deepest > DEFAULT_TRIAL_TILE and trial_grid_vmem_bytes(
            deepest, n_servers, window_size, n_windows,
            plan) > SCOPED_VMEM_BYTES:
        deepest -= SUBLANES
    programs = -(-per // deepest)
    return -(-per // programs)


# Sublane budget of the FUSED multi-trial client block (DESIGN.md §16):
# when the client tile resolves small (a 4-client stream fills 4 of the
# 32 sublanes DEFAULT_CLIENT_TILE aims at), `resolve_grid_tiles` deepens
# the TRIAL tile until the block's stream-sublane count tt*ct reaches
# this budget — packing multiple trials into one sublane tile instead of
# wasting the lanes, and cutting the grid's program count (the dominant
# cost under interpret mode, where dispatch overhead is per program).
FUSED_SUBLANE_BUDGET = 64


def resolve_grid_tiles(n_trials: int, n_clients: int, trial_tile=None,
                       client_tile=None) -> tuple:
    """Joint (trial_tile, client_tile) of the fused multi-trial client
    block.  The client tile resolves exactly as `resolve_client_tile`;
    an unset trial tile then deepens to fill `FUSED_SUBLANE_BUDGET`
    stream sublanes (never below the default).  Both values remain
    ASSOCIATION parameters: every layer (kernel grid, engine twin, jax
    cross-client fold, sharded sweep) must consume the pair this
    function returns — resolving either half anywhere else risks two
    layers disagreeing on the merge association (DESIGN.md §12/§16)."""
    ct = resolve_client_tile(n_clients, client_tile)
    if trial_tile is None:
        trial_tile = max(FUSED_SUBLANE_BUDGET // ct, DEFAULT_TRIAL_TILE)
    return resolve_trial_tile(n_trials, trial_tile), ct

# The in-kernel LCG (numerical recipes constants) — also used by the JAX
# engine when ``PolicyConfig.rng == "lcg"`` so kernel and engine consume
# an identical randomness stream (the bit-exactness contract).
LCG_A = 1664525
LCG_C = 1013904223
_MASK32 = 0xFFFFFFFF


def pack(loads, probs, ewma_lat, est_rates, xp=jnp):
    """Stack the four rows into one (4, M) table."""
    return xp.stack([loads, probs, ewma_lat, est_rates])


def init_table(m: int, xp=jnp, dtype=None, batch=None):
    """Fresh log: zero loads, round-robin prior p_i = 1/M (paper §3.3.2),
    no observations, optimistic unit estimated rates (= ect_rates(0)).

    ``batch`` adds a leading trial axis — a ``(batch, 4, M)`` stack of
    independent fresh logs, the layout the trial-grid kernel slices per
    program instance (also used to pad a trial batch up to the grid
    tile with inert-but-finite tables)."""
    shape = (N_ROWS, m) if batch is None else (batch, N_ROWS, m)
    dtype = dtype or (jnp.float32 if xp is jnp else np.float64)
    t = xp.zeros(shape, dtype)
    if xp is np:
        t[..., ROW_PROBS, :] = 1.0 / m
        t[..., ROW_EST, :] = 1.0
        return t
    return (t.at[..., ROW_PROBS, :].set(1.0 / m)
            .at[..., ROW_EST, :].set(1.0))


# ---------------------------------------------------------------------------
# Shared LCG (kernel randomness, mirrored by the engine's rng="lcg" mode)
# ---------------------------------------------------------------------------


def lcg_step(rng, xp=jnp):
    """One LCG step on a uint32 state."""
    if xp is np:
        return (int(rng) * LCG_A + LCG_C) & _MASK32
    return rng * jnp.uint32(LCG_A) + jnp.uint32(LCG_C)


def lcg_mod(rng, n: int, xp=jnp):
    """Map an LCG state to [0, n): drop the low byte (weak low bits),
    mask to non-negative int32, take the remainder."""
    if xp is np:
        return ((int(rng) >> 8) & 0x7FFFFFFF) % n
    return jax.lax.rem((rng >> jnp.uint32(8)).astype(jnp.int32)
                       & jnp.int32(0x7FFFFFFF), n)


def two_random_draws(rng, n: int, xp=jnp):
    """Two consecutive LCG draws in [0, n); returns (d1, d2, new_rng).

    This is the exact draw sequence of the kernel's ``two_random`` and
    ``trh`` policies — the engine's rng="lcg" mode replays it bit-for-bit.
    """
    r1 = lcg_step(rng, xp)
    r2 = lcg_step(r1, xp)
    return lcg_mod(r1, n, xp), lcg_mod(r2, n, xp), r2


# ---------------------------------------------------------------------------
# Decision core: scores, target selection, threshold guard
# ---------------------------------------------------------------------------


def ect_rates(ewma_lat, xp=jnp):
    """Client-estimated service rates (the ``est_rates`` row) from the
    observation EWMA alone.  Unobserved servers get the best seen rate
    (optimistic initialization -> exploration); an empty log estimates
    1 MB/s everywhere (the static model where MB and seconds coincide).

    By construction this never reads the true ``rates`` — the stale-view
    contract (DESIGN.md §8), property-tested in tests/test_statlog.py.
    """
    default = xp.maximum(xp.max(ewma_lat), 1.0)
    return xp.where(ewma_lat > 0, ewma_lat, default)


def ect_scores(loads, est_rates, length, xp=jnp):
    """Expected completion time per server: (load_i + len) / est_rate_i.
    Scored on the client's ESTIMATED rates, never the true ones."""
    return (loads + length) / est_rates


def redirect_benefit(policy_name: str, loads, est_rates, default, target,
                     length, xp=jnp):
    """Paper's §3.4.1 redirect guard benefit: MB of load for the load-based
    policies, expected seconds for the rate-aware ECT extension."""
    if policy_name == "ect":
        return ((loads[default] + length) / est_rates[default]
                - (loads[target] + length) / est_rates[target])
    return loads[default] - loads[target]


def _next_pow2(n: int) -> int:
    size = 1
    while size < n:
        size *= 2
    return size


def _bitonic_network(keys, idx, payloads, xp, descending: bool):
    """Run the textbook bitonic schedule on pre-padded power-of-two lanes.

    ``keys``/``idx`` order the elements by ``(key desc|asc, index asc)``
    — a strict total order either way; every compare-exchange also moves
    the ``payloads`` lanes with the SAME swap mask, so payload values are
    only ever relocated by selects (never combined arithmetically) and
    land bit-identical to a take along the resulting permutation
    (DESIGN.md §13).  Each stage is two circular rolls plus selects —
    fixed elementwise HLO, no gather, legal inside a fused Pallas body.
    """
    pos = idx
    rp = keys.shape[-1]
    payloads = list(payloads)
    k = 2
    while k <= rp:
        asc = (pos & k) == 0          # comparator-ascending region
        j = k // 2
        while j >= 1:
            is_lo = (pos & j) == 0    # lower element of each (i, i^j) pair
            # partner values: i^j == i+j (lo) / i-j (hi) — two rolls; the
            # wrapped lanes are never selected by the is_lo mask.
            pk = xp.where(is_lo, xp.roll(keys, -j, axis=-1),
                          xp.roll(keys, j, axis=-1))
            pi = xp.where(is_lo, xp.roll(idx, -j, axis=-1),
                          xp.roll(idx, j, axis=-1))
            # partner ranks before self in (key desc|asc, index asc) order
            if descending:
                p_first = (pk > keys) | ((pk == keys) & (pi < idx))
            else:
                p_first = (pk < keys) | ((pk == keys) & (pi < idx))
            swap = xp.where(asc == is_lo, p_first, ~p_first)
            keys = xp.where(swap, pk, keys)
            idx = xp.where(swap, pi, idx)
            for n, p in enumerate(payloads):
                pp = xp.where(is_lo, xp.roll(p, -j, axis=-1),
                              xp.roll(p, j, axis=-1))
                payloads[n] = xp.where(swap, pp, p)
            j //= 2
        k *= 2
    return keys, idx, tuple(payloads)


def _sort_iota(shape, xp):
    if xp is np:
        return np.broadcast_to(np.arange(shape[-1], dtype=np.int32), shape)
    # broadcasted_iota, not arange: 1-D iota does not lower inside
    # TPU Pallas bodies (this runs in the kernel too)
    return jax.lax.broadcasted_iota(jnp.int32, shape, len(shape) - 1)


def bitonic_sort_with_payload(keys, payloads=(), valid=None, xp=jnp):
    """Stable descending sort as an EXPLICIT bitonic compare-exchange
    network, carrying ``payloads`` through every compare-exchange — the
    in-VMEM sort of DESIGN.md §10 extended with the permutation-apply
    fast path of §13.

    ``keys``: (..., R) sort keys; ``valid`` (same shape, optional) masks
    rows to ``-inf`` keys so they sink to the end; each payload has the
    keys' shape and any dtype.  The last axis pads to the next power of
    two with ``-inf`` keys, continuing indices and zero payloads, then
    runs the bitonic schedule (outer width ``k = 2..R_pad``, inner
    stride ``j = k/2..1``).

    The comparator orders by ``(key desc, index asc)`` — a strict total
    order, so ANY correct network yields the one permutation that equals
    ``argsort(-keys, stable)``; using the same schedule in the engine,
    the host twin and the kernel makes the match structural rather than
    coincidental (like :func:`lane_sum`).  Payloads are moved by the
    same swaps, so ``sorted_payloads[i] == payload[order[i]]`` exactly
    (property-pinned against stable argsort + take in
    tests/test_policies.py); the R real elements always sort before the
    R_pad - R padding, so positions ``< R`` never see a padding payload.

    Returns ``(order, sorted_keys, sorted_payloads)``: ``order`` int32
    (..., R_pad) maps sorted position -> original index (positions
    ``>= R`` are padding); ``sorted_keys`` are the masked keys in that
    order (``-inf`` at invalid/padding positions); ``sorted_payloads``
    the payload tuple in that order.
    """
    r = keys.shape[-1]
    rp = _next_pow2(r)
    neg = xp.asarray(-xp.inf, keys.dtype)
    if valid is not None:
        keys = xp.where(valid, keys, neg)
    if rp != r:
        pad = [(0, 0)] * (keys.ndim - 1) + [(0, rp - r)]
        keys = xp.pad(keys, pad, constant_values=-xp.inf)
        payloads = tuple(xp.pad(p, pad) for p in payloads)
    idx = _sort_iota(keys.shape, xp)
    keys, idx, payloads = _bitonic_network(keys, idx, payloads, xp,
                                           descending=True)
    return (idx.astype(np.int32 if xp is np else jnp.int32), keys,
            payloads)


def bitonic_argsort_desc(keys, valid=None, xp=jnp):
    """Stable descending argsort — :func:`bitonic_sort_with_payload`
    with no payload lanes.  Returns ``(order, sorted_keys)``."""
    order, skeys, _ = bitonic_sort_with_payload(keys, (), valid=valid, xp=xp)
    return order, skeys


def bitonic_apply_inverse(order, payloads, xp=jnp):
    """Apply the INVERSE of a sort permutation to payload lanes — the
    one permutation apply per window of DESIGN.md §13.

    ``order``: (..., R_pad) int32 permutation of ``0..R_pad-1`` mapping
    sorted position -> original index (a ``bitonic_sort_with_payload``
    order, R_pad a power of two); ``payloads``: tuple of (..., R_pad)
    arrays in SORTED order.  Returns the payloads moved back to
    ORIGINAL-index order, i.e. ``out[order[p]] = payload[p]``, as one
    ascending bitonic pass keyed on the distinct integers of ``order``
    (strict total order, so the network computes THE unique inverse).
    Values are only relocated — never combined — so the result equals
    the one-hot scatter oracle bit-for-bit (property-pinned in
    tests/test_policies.py); no scatter/gather op, legal inside a fused
    Pallas body.
    """
    idx = _sort_iota(order.shape, xp)
    _, _, payloads = _bitonic_network(order, idx, payloads, xp,
                                      descending=False)
    return payloads


# Lane block of the all-pairs rank and its permutation applies (DESIGN.md
# §19): one vreg's 128 lanes.  A row wider than one block is cut into
# ceil(R / 128) blocks on BOTH axes of the all-pairs tile, and the blocks
# are walked by a loop, so the live tile is (..., 128, 128) at any width;
# a row of at most 128 is one block: the whole-tile form itself.
RANK_BLOCK = 128


def _n_blocks(r: int) -> int:
    return -(-r // RANK_BLOCK)


def _pad_row(x, n: int, value, xp):
    """Pad the last axis to ``n`` whole blocks with ``value``."""
    width = n * RANK_BLOCK
    if n == 1 or x.shape[-1] == width:
        return x
    pad = [(0, 0)] * (x.ndim - 1) + [(0, width - x.shape[-1])]
    return xp.pad(x, pad, constant_values=value)


def _block_loop(n: int, body, init, xp):
    """``acc = body(b, acc)`` for the blocks ``b = 0 .. n-1``: a Python
    loop over static blocks in numpy, one ``fori_loop`` over a traced
    block in jnp — a loop, not n unrolled copies, so a Pallas body keeps
    one block's tile live (Mosaic keeps every unrolled tile)."""
    if xp is np:
        for b in range(n):
            init = body(b, init)
        return init
    return jax.lax.fori_loop(0, n, body, init)


def _take_block(x, b, n: int, xp):
    """Lanes ``[128 b, 128 b + 128)`` of a row of ``n`` whole blocks.  A
    traced ``b`` picks the block with a select chain over the n static
    blocks: exact for any value, and no lane offset Mosaic must prove."""
    if xp is np:
        return x[..., b * RANK_BLOCK:(b + 1) * RANK_BLOCK]
    out = x[..., :RANK_BLOCK]
    for k in range(1, n):
        out = jnp.where(b == k, x[..., k * RANK_BLOCK:(k + 1) * RANK_BLOCK],
                        out)
    return out


def _place_block(row, blk, b, n: int):
    """``row`` with its block ``b`` (traced) replaced by ``blk``: one
    select against the block repeated over the row."""
    lane_block = jax.lax.broadcasted_iota(jnp.int32, row.shape,
                                          row.ndim - 1) // RANK_BLOCK
    return jnp.where(lane_block == b, jnp.concatenate([blk] * n, axis=-1),
                     row)


def _fill_blocks(n: int, block, rows, xp):
    """Rows of ``n`` whole blocks, shaped and typed as the tuple ``rows``,
    whose block ``b`` is the tuple ``block(b)``."""
    if xp is np:
        parts = [block(b) for b in range(n)]
        return tuple(np.concatenate(col, axis=-1) for col in zip(*parts))
    return jax.lax.fori_loop(
        0, n, lambda b, acc: tuple(_place_block(row, blk, b, n)
                                   for blk, row in zip(block(b), acc)),
        tuple(jnp.zeros_like(x) for x in rows))


def _block_lanes(lane, b):
    """Row indices of the lanes of block ``b``: ``lane`` is the iota of
    one block (int32, exact)."""
    offset = b * RANK_BLOCK
    return lane + offset


def _sum_blocks(n: int, term, blocks, xp):
    """``sum_b term(b)`` over the n blocks, from exact zeros shaped and
    typed as the tuple ``blocks``.  Used only for integer counts, or
    where one block holds the one non-zero term of each lane: exact in
    any order."""
    return _block_loop(
        n, lambda b, acc: tuple(x + t for x, t in zip(acc, term(b))),
        tuple(xp.zeros_like(x) for x in blocks), xp)


def _count_before(a, ia, b, ib, i32, xp):
    """Per self element, how many others precede it in ``(key desc,
    index asc)``: self on axis -2, others on the last axis."""
    before = (b > a) | ((b == a) & (ib < ia))
    return xp.sum(before.astype(i32), axis=-1)


def rank_desc(keys, valid=None, xp=jnp):
    """Rank of every element under ``(key desc, index asc)`` — the sort
    permutation WITHOUT running a sort network (DESIGN.md §13).

    ``rank[i] = #{k : key_k > key_i  or  (key_k == key_i and k < i)}`` —
    one broadcasted all-pairs comparison over an ``(..., R, R)`` tile
    plus an integer row count.  The comparator is the strict total order
    shared with :func:`bitonic_sort_with_payload`, so ``rank`` is exactly
    the INVERSE of the stable ``argsort(-keys)`` permutation: element
    ``i`` lands at sorted position ``rank[i]``.  ``valid`` masks keys to
    ``-inf`` first (invalid rows rank after every valid one, index-asc
    among themselves — the §10 ordering invariant).  Integer compares and
    counts only — bit-exact on every backend, and unlike the network this
    needs no power-of-two padding.

    Wider than :data:`RANK_BLOCK` (DESIGN.md §19), the tile is taken one
    ``(..., 128, 128)`` block pair at a time: each self block sums the
    integer counts of every other block.  Padding lanes carry ``-inf``
    keys and indices past R, so they never precede a real element, and
    the rank is the same integers at any block count.

    Returns ``(rank, masked_keys)``: ``rank`` int32 (..., R), and the
    keys after the validity mask (``-inf`` at invalid rows — the
    ``sorted_keys`` source for :func:`permute_to_sorted`).
    """
    i32 = np.int32 if xp is np else jnp.int32
    if valid is not None:
        keys = xp.where(valid, keys, xp.asarray(-xp.inf, keys.dtype))
    r = keys.shape[-1]
    n = _n_blocks(r)
    if n == 1:
        idx = _sort_iota(keys.shape, xp)
        return _count_before(keys[..., :, None], idx[..., :, None],
                             keys[..., None, :], idx[..., None, :], i32,
                             xp), keys
    padded = _pad_row(keys, n, -xp.inf, xp)
    lane = _sort_iota(keys.shape[:-1] + (RANK_BLOCK,), xp)

    def self_block(i):
        a = _take_block(padded, i, n, xp)[..., :, None]
        ia = _block_lanes(lane, i)[..., :, None]
        return _sum_blocks(
            n, lambda j: (_count_before(
                a, ia, _take_block(padded, j, n, xp)[..., None, :],
                _block_lanes(lane, j)[..., None, :], i32, xp),),
            (lane,), xp)

    (rank,) = _fill_blocks(n, self_block, (xp.zeros(padded.shape, i32),),
                           xp)
    return rank[..., :r], keys


def rank_asc(keys):
    """Rank of every element under ``(key asc, index asc)`` — the integer
    mirror of :func:`rank_desc` (DESIGN.md §18).

    ``rank[i] = #{k : key_k < key_i  or  (key_k == key_i and k < i)}``,
    the inverse of the stable ``argsort(keys)`` permutation, from one
    all-pairs integer comparison over an ``(..., R, R)`` tile.  Also
    returns ``n_less[i] = #{k : key_k < key_i}``: the sorted position of
    the first element of ``i``'s run of equal keys, so ``i`` is the
    first of its run exactly where ``rank[i] == n_less[i]``.  Keys are
    compared as they are (int32 object ids never pass through float).
    """
    idx = _sort_iota(keys.shape, jnp)
    a, ia = keys[..., :, None], idx[..., :, None]         # self
    b, ib = keys[..., None, :], idx[..., None, :]         # other
    n_less = jnp.sum((b < a).astype(jnp.int32), axis=-1)
    ties_before = jnp.sum(((b == a) & (ib < ia)).astype(jnp.int32), axis=-1)
    return n_less + ties_before, n_less


def _rank_onehot(rank, xp):
    """(..., i, p) boolean: element ``i`` occupies sorted position ``p``."""
    pos = _sort_iota(rank.shape, xp)
    return rank[..., :, None] == pos[..., None, :]


def permute_to_sorted(rank, payloads, xp=jnp, whole_tile=False):
    """Gather payload lanes into sorted order: ``out[p] = payload[i]``
    where ``rank[i] == p`` (DESIGN.md §13).

    ``rank`` is a :func:`rank_desc` permutation, so exactly ONE element
    maps to each position: the masked sum below has a single non-zero
    term per output lane and is therefore a pure relocation — bit-exact
    for floats too (``x + 0.0 == x``; no value here is ``-0.0``).  One
    ``(..., R, R)`` select + sum per payload, no gather op, no sort
    network — legal inside a fused Pallas body.  Wider than
    :data:`RANK_BLOCK` (DESIGN.md §19), each 128-position block of the
    output sums the blocks of the input one ``(..., 128, 128)`` tile at a
    time; the one non-zero term still lands unchanged.  ``whole_tile``
    keeps the one tile at any width: XLA fuses it into its reductions
    and never writes it out, so the engine's grouping takes it (§18); a
    Pallas body would hold it in VMEM.
    """
    r = rank.shape[-1]
    n = 1 if whole_tile else _n_blocks(r)
    if n == 1:
        oh = _rank_onehot(rank, xp)
        return tuple(
            xp.sum(xp.where(oh, x[..., :, None], xp.zeros((), x.dtype)),
                   axis=-2) for x in payloads)
    rank = _pad_row(rank, n, -1, xp)        # padding lands nowhere
    lane = _sort_iota(rank.shape[:-1] + (RANK_BLOCK,), xp)
    xs = tuple(_pad_row(x, n, 0, xp) for x in payloads)

    def position_block(p):
        pos = _block_lanes(lane, p)[..., None, :]

        def term(i):
            oh = _take_block(rank, i, n, xp)[..., :, None] == pos
            return tuple(xp.sum(xp.where(oh, _take_block(x, i, n, xp)[
                ..., :, None], xp.zeros((), x.dtype)), axis=-2) for x in xs)

        return _sum_blocks(n, term, tuple(x[..., :RANK_BLOCK] for x in xs),
                           xp)

    return tuple(x[..., :r] for x in _fill_blocks(n, position_block, xs, xp))


def permute_from_sorted(rank, payloads, xp=jnp, whole_tile=False):
    """Scatter sorted payload lanes back to original-index order:
    ``out[i] = payload[rank[i]]`` — the inverse apply of DESIGN.md §13,
    same single-non-zero-term masked sum as :func:`permute_to_sorted`,
    blocked the same way past :data:`RANK_BLOCK` (property-pinned against
    the one-hot scatter oracle in tests/test_policies.py).  Each output
    lane reads one position, so ``rank`` need not be a permutation: any
    in-range index row makes this an exact take (DESIGN.md §18 relocates
    step results to their requests with it).  ``whole_tile`` as in
    :func:`permute_to_sorted`."""
    r = rank.shape[-1]
    n = 1 if whole_tile else _n_blocks(r)
    if n == 1:
        oh = _rank_onehot(rank, xp)
        return tuple(
            xp.sum(xp.where(oh, x[..., None, :], xp.zeros((), x.dtype)),
                   axis=-1) for x in payloads)
    rank = _pad_row(rank, n, -1, xp)
    lane = _sort_iota(rank.shape[:-1] + (RANK_BLOCK,), xp)
    xs = tuple(_pad_row(x, n, 0, xp) for x in payloads)

    def index_block(i):
        ri = _take_block(rank, i, n, xp)[..., :, None]

        def term(p):
            oh = ri == _block_lanes(lane, p)[..., None, :]
            return tuple(xp.sum(xp.where(oh, _take_block(x, p, n, xp)[
                ..., None, :], xp.zeros((), x.dtype)), axis=-1) for x in xs)

        return _sum_blocks(n, term, tuple(x[..., :RANK_BLOCK] for x in xs),
                           xp)

    return tuple(x[..., :r] for x in _fill_blocks(n, index_block, xs, xp))


def recursive_average_bounds(sorted_len, nvalid, n_levels: int, xp=jnp):
    """nLTR §3.4.3 request sectioning on a desc-sorted length list: split
    ``[0, nvalid)`` into ``2^n_levels`` sections by recursive average.

    ``sorted_len``: (..., R) lengths in descending order (padding beyond
    ``nvalid`` never read); ``nvalid``: (..., 1) int32 count of valid
    rows.  Returns (..., K-1) int32 boundary indices in tree (BFS) order
    — section of position ``p`` is ``sum(bounds <= p)`` (order-free, so
    callers never need them sorted).

    Every float reduction goes through :func:`lane_sum` so the engine's
    per-window call, the oracle and the kernel's ``(t_tile, R_pad)``
    tile form associate the section means identically — a mean that
    drifts 1 ulp can flip an integer boundary, which the bit-exactness
    contract (DESIGN.md §10) cannot absorb.  All boundary arithmetic is
    int32 (exact everywhere).
    """
    r = sorted_len.shape[-1]
    i32 = np.int32 if xp is np else jnp.int32
    if xp is np:
        pos = np.arange(r, dtype=np.int32)
    else:  # kernel-legal iota (see bitonic_argsort_desc)
        pos = jax.lax.broadcasted_iota(jnp.int32, sorted_len.shape,
                                       sorted_len.ndim - 1)
    zero = xp.zeros_like(nvalid)
    starts = [zero]
    ends = [nvalid.astype(i32)]
    bounds = []
    for _ in range(n_levels):
        new_starts, new_ends = [], []
        for s, e in zip(starts, ends):
            inside = (pos >= s) & (pos < e)
            cnt = xp.maximum(xp.sum(inside, axis=-1, keepdims=True), 1)
            # zeros_like, NOT 0.0 * sorted_len: the padded tail carries
            # -inf sort keys and 0 * -inf would leak NaN into the sum
            mean = lane_sum(xp.where(inside, sorted_len,
                                     xp.zeros_like(sorted_len)), xp) / cnt
            # desc order: elements > mean come first; boundary = first
            # index with value <= mean inside [s, e)
            gt = inside & (sorted_len > mean)
            b = s + xp.sum(gt, axis=-1, keepdims=True).astype(i32)
            # keep the boundary strictly inside (s, e): no empty section
            b = xp.clip(b, s + (e > s + 1), xp.maximum(e - 1, s + 1))
            bounds.append(b)
            new_starts.extend([s, b])
            new_ends.extend([b, e])
        starts, ends = new_starts, new_ends
    return xp.concatenate(bounds, axis=-1)


# ---------------------------------------------------------------------------
# Eq. (1)-(3) updates, observation, window maintenance
# ---------------------------------------------------------------------------


def assignment_update(loads, probs, server, length, lam: float, m: int,
                      xp=jnp):
    """Eq. (1)-(3): book ``length`` MB on ``server``; decay its selection
    probability and spread the lost mass over the other M-1 servers.

    The jnp form uses one-hot vector writes (`where`) instead of scatter
    — the exact formulation the Pallas kernel executes on VMEM lanes, so
    XLA lowers both layers through the same elementwise ops and the
    engine<->kernel trace stays bit-identical (scatter + scalar-exp
    lowering was observed to differ by 1 ulp inside fused loop bodies).

    Eq. (3)'s redistributed mass is computed as ``p_i * (1 - e) / (M-1)``
    rather than the algebraically equal ``(p_i - p_i * e) / (M-1)``: the
    latter is a mul-feeding-sub that XLA/LLVM contracts into an FMA in
    some lowering contexts and not others (observed tile-dependent in the
    trial-grid kernel — DESIGN.md §9), while here every product feeds a
    select or a divide, which nothing contracts.
    """
    if xp is np:
        loads = loads.copy()
        probs = probs.copy()
        loads[server] += length                              # Eq. (1)
        p_i = probs[server]
        e = np.exp(-loads[server] / lam)
        decayed = p_i * e                                    # Eq. (2)
        delta = p_i * (1.0 - e) / (m - 1)                    # Eq. (3)
        probs += delta
        probs[server] = decayed
        return loads, probs
    onehot = jnp.arange(loads.shape[-1]) == server
    loads = jnp.where(onehot, loads + length, loads)         # Eq. (1)
    l_i = loads[server]
    p_i = probs[server]
    e = jnp.exp(-l_i / lam)
    decayed = p_i * e                                        # Eq. (2)
    delta = p_i * (1.0 - e) / (m - 1)                        # Eq. (3)
    probs = jnp.where(onehot, decayed, probs + delta)
    return loads, probs


def observe_update(ewma_lat, server, mb_per_s, alpha: float, xp=jnp):
    """Fold one observed service rate into the EWMA row and re-derive the
    estimated-rate row.  Returns (ewma_lat, est_rates).  The est row is a
    pure function of observations — the only way the client ever learns
    about a server's speed (stale-view contract)."""
    if xp is np:
        ewma_lat = ewma_lat.copy()
        old = ewma_lat[server]
        ewma_lat[server] = (mb_per_s if old == 0.0
                            # contract-ok: CC-FMA EWMA row is 1e-6-soft (§9)
                            else (1 - alpha) * old + alpha * mb_per_s)
    else:
        old = ewma_lat[server]
        new = jnp.where(old == 0.0, mb_per_s,
                        # contract-ok: CC-FMA EWMA row is 1e-6-soft (§9)
                        (1 - alpha) * old + alpha * mb_per_s)
        ewma_lat = ewma_lat.at[server].set(new)
    return ewma_lat, ect_rates(ewma_lat, xp)


def lane_sum(x, xp=jnp):
    """Deterministic last-axis sum: an EXPLICIT pairwise halving tree
    (pad to the next power of two with exact zeros, then repeatedly add
    the upper half onto the lower).  ``jnp.sum``'s reduction tree is a
    backend/shape-dependent lowering choice — the trial-grid kernel's
    ``(t_tile, 128)`` row sum was observed to associate differently from
    the engine's ``(M,)`` sum, a 1-ulp drift per window that breaks the
    §9 parity contract.  Explicit adds are fixed HLO ops no backend may
    reassociate, and leading halvings over all-zero upper halves are
    exact identities, so any zero-padded width yields the same bits.
    Returns shape (..., 1)."""
    # contract-ok: CC-TWIN np arm IS the f64 host oracle (§9)
    if xp is np:
        # contract-ok: CC-SUM host-twin sum is the reference association (§9)
        return x.sum(axis=-1, keepdims=True)
    m = x.shape[-1]
    size = 1
    while size < m:
        size *= 2
    if size != m:
        pad = [(0, 0)] * (x.ndim - 1) + [(0, size - m)]
        x = jnp.pad(x, pad)
    while x.shape[-1] > 1:
        h = x.shape[-1] // 2
        x = x[..., :h] + x[..., h:]
    return x


def renormalize_probs(probs, xp=jnp):
    """Re-project the probability row onto the simplex (float-drift guard;
    run once per window by every layer that renormalizes).

    The reduction runs through :func:`lane_sum` so the engine, the oracle
    and the (tiled) kernel all associate the sum identically — the last
    bit of the engine<->kernel parity contract."""
    # contract-ok: CC-TWIN np arm IS the f64 host oracle (§9)
    if xp is np:
        p = np.clip(probs, 0.0, None)
        # contract-ok: CC-SUM host-twin sum is the reference association (§9)
        return p / p.sum(axis=-1, keepdims=True)
    p = jnp.clip(probs, 0.0)
    return p / lane_sum(p)


def absorb_probs(loads, lam: float, m: int, xp=jnp):
    """Probability row absorbing known initial loads — the vectorized
    fixed point of Eq. (2): ``p_i ∝ (1/M) · e^{-l_i/λ}`` (DESIGN.md §14).

    The normalization runs through :func:`lane_sum` so the batched trial
    prep (vmapped over the trial axis, ``(T, M)`` rows) and the
    sequential ``lax.map`` prep (``(M,)`` rows) associate the sum
    identically — the halving tree is batch-shape-invariant, whereas
    ``jnp.sum``'s reduction tree is a lowering choice that may differ
    between the two contexts.  Works on any ``(..., M)`` batch."""
    # contract-ok: CC-TWIN np arm IS the f64 host oracle (§9)
    if xp is np:
        p = np.exp(-loads / lam) / m
        # contract-ok: CC-SUM host-twin sum is the reference association (§9)
        return p / p.sum(axis=-1, keepdims=True)
    p = jnp.exp(-loads / lam) / m
    return p / lane_sum(p)


def server_segment_sum(values, idx, m: int, xp=jnp, block: int = 128):
    """Pinned per-server float sum: ``out[s] = Σ values[r] · [idx[r] == s]``
    with an EXPLICIT association no backend may reshuffle — sequential
    (ascending) over ``ceil(R / block)`` request chunks, each chunk's
    one-hot contributions folded by :func:`tree_sum` over the request
    axis (DESIGN.md §14).

    ``jax.ops.segment_sum`` lowers to a scatter-add whose duplicate-index
    combine order is a backend choice that may differ between the vmapped
    batched post step and the per-trial ``lax.map`` oracle; this
    formulation is the same in both contexts by construction (the chunk
    walk mirrors `masked_client_sum`'s sequential-over-blocks /
    tree-within-block shape).  Integer sums don't need it — they are
    exact under any order.  ``values``/``idx``: (..., R); returns
    (..., m)."""
    r = values.shape[-1]
    n_blocks = max(-(-r // block), 1)
    if xp is np:
        lane = np.arange(m, dtype=np.int64)
    else:
        lane = jnp.arange(m, dtype=jnp.int32)
    out = None
    for b in range(n_blocks):
        v = values[..., b * block:(b + 1) * block]
        i = idx[..., b * block:(b + 1) * block]
        onehot = i[..., :, None] == lane            # (..., blk, m)
        contrib = xp.where(onehot, v[..., :, None], xp.zeros_like(v)[..., None])
        blk_sum = tree_sum(contrib, axis=-2, xp=xp)[..., 0, :]
        out = blk_sum if out is None else out + blk_sum
    return out


def window_decrements(rates, dt, xp=jnp):
    """Per-window drain decrement ``max(rates, 1e-6) * dt`` — computed
    ONCE, outside the fused loop body that subtracts it.

    This materialization is a correctness contract, not a micro-opt
    (DESIGN.md §9): when the product sits next to the subtraction inside
    one fused computation, XLA/LLVM may contract ``loads - rates * dt``
    into an FMA — and whether it does was observed to depend on the
    lowering context (the scan-body engine and the t_tile = 1 kernel
    fused; the trial-tiled kernel did not), a 1-ulp drift that breaks
    the engine<->kernel bit-exactness contract.  A decrement that enters
    the loop as a materialized array (scan ``xs`` row / pallas operand)
    leaves only a bare subtract inside the body, which every backend
    rounds identically.

    The scan-xs materialization alone is NOT sufficient: XLA simplifies
    a single-iteration window scan away, the orphaned product lands in
    the same kLoop fusion as the subtract, and LLVM contracts the pair
    into an FMA at instruction selection — a level no graph construct
    reaches (``optimization_barrier`` and even an int32 bitcast
    round-trip were both observed to contract anyway; found under the
    per_client vmap² engine at one-window-per-client shapes, DESIGN.md
    §11).  The fix is arithmetic: clamp the decrement at zero.  A drain
    decrement is nonnegative by construction, so ``maximum(dec, 0)`` is
    a bit-exact identity — but the subtract's operand is now a
    ``maximum``, not a ``multiply``, and fp contraction only fuses a
    multiply that DIRECTLY feeds the add/sub (the compiler cannot drop
    the clamp either: the rates are runtime values whose sign it cannot
    prove)."""
    return xp.maximum(xp.maximum(rates, 1e-6) * dt, 0.0)


def drain_loads(loads, rates, dt, xp=jnp, dec=None):
    """Temporal model: drain each server's outstanding queue at its TRUE
    service rate for ``dt`` virtual seconds, clipped at empty.  The one
    place the simulator's ground-truth rates touch the log (queue physics,
    not a scheduling decision).

    ``dec`` is the precomputed :func:`window_decrements` row; pass it
    whenever the drain runs inside a fused loop body (see that helper's
    FMA-contraction note).  ``dec=None`` computes it inline — fine for
    the numpy host twin and one-shot jnp calls."""
    if dec is None:
        dec = window_decrements(rates, dt, xp)
    return xp.maximum(loads - dec, 0.0)


def estimated_latency(loads, rates, server, xp=jnp):
    """Seconds until a request just queued on ``server`` completes, at the
    given (true) service rates — the simulator's latency report."""
    return loads[server] / xp.maximum(rates[server], 1e-6)


# ---------------------------------------------------------------------------
# Fused stream metrics — the trial-grid kernel's in-VMEM reduction twin
# ---------------------------------------------------------------------------

P99_Q = 0.99          # nearest-rank quantile the kernel reduces in-VMEM
P99_BISECT_ITERS = 48  # f32 bisection steps (converges to lane adjacency)


def nearest_rank_p99(lats, valid, xp=jnp):
    """Nearest-rank p99 of the valid latencies via value bisection — the
    EXACT float algorithm the kernel runs on its VMEM-resident latency
    block (DESIGN.md §9): ``P99_BISECT_ITERS`` halvings of ``[-1, max]``
    keeping ``count(lats <= lo) < k <= count(lats <= hi)`` with
    ``k = ceil(0.99 * n_valid)``, then the smallest element above ``lo``.
    Supports a leading batch axis; all arithmetic is f32 so the kernel
    and this twin agree bit-for-bit.
    """
    lats = lats.astype(jnp.float32) if xp is jnp else lats.astype(np.float32)
    validf = valid.astype(lats.dtype)
    # contract-ok: CC-SUM counting exact 0/1 floats — every association agrees (§9)
    nval = xp.sum(validf, axis=-1, keepdims=True)
    k = xp.ceil(lats.dtype.type(P99_Q) * nval) if xp is np \
        else xp.ceil(jnp.float32(P99_Q) * nval)
    lo = xp.full(nval.shape, -1.0, lats.dtype)
    hi = xp.max(xp.where(valid, lats, 0.0), axis=-1, keepdims=True)
    for _ in range(P99_BISECT_ITERS):
        mid = lats.dtype.type(0.5) * (lo + hi) if xp is np \
            else jnp.float32(0.5) * (lo + hi)
        cnt = xp.sum(xp.where(valid & (lats <= mid), validf, 0.0 * validf),
                     axis=-1, keepdims=True)
        go_hi = cnt >= k
        lo, hi = xp.where(go_hi, lo, mid), xp.where(go_hi, mid, hi)
    big = lats.dtype.type(3.4e38)
    p99 = xp.min(xp.where(valid & (lats > lo), lats, big),
                 axis=-1, keepdims=True)
    return xp.where(nval > 0, p99, 0.0 * p99)


def stream_metrics(lats, valid, window_dt: float, window_size: int, xp=jnp):
    """Per-trial fused metrics over a scheduled stream, in the EXACT
    accumulation order of the trial-grid kernel (request order for the
    order-sensitive ``lat_sum``; ``makespan``/``lat_max``/``n_valid`` are
    order-free reductions; ``p99_lat`` via :func:`nearest_rank_p99`).

    ``lats``/``valid``: (..., N) per-step latencies and validity with
    ``N = W * window_size``; completion of step ``i`` is
    ``(i // window_size) * window_dt + lat_i`` (the simulator's
    window-open clock).  Returns (..., N_METRICS) f32 in ``MET_*`` order.
    """
    lats = lats.astype(jnp.float32 if xp is jnp else np.float32)
    latv = xp.where(valid, lats, 0.0 * lats)
    n = lats.shape[-1]
    idx = xp.arange(n, dtype=np.int32 if xp is np else jnp.int32)
    # f32 cast BEFORE the multiply — the kernel's wopen = f32(w) * f32(dt)
    w_open = (idx // window_size).astype(lats.dtype) * lats.dtype.type(
        window_dt) if xp is np else \
        (idx // window_size).astype(jnp.float32) * jnp.float32(window_dt)
    makespan = xp.max(xp.where(valid, w_open + lats, 0.0 * lats),
                      axis=-1, keepdims=True)
    lat_max = xp.max(latv, axis=-1, keepdims=True)
    n_valid = xp.sum(xp.where(valid, xp.ones_like(latv), 0.0 * latv),
                     axis=-1, keepdims=True)
    if xp is np:
        lat_sum = np.zeros(latv.shape[:-1] + (1,), np.float32)
        for i in range(n):                       # sequential f32 adds —
            lat_sum = lat_sum + latv[..., i:i + 1]   # the kernel's order
    else:
        lat_sum = jax.lax.fori_loop(
            0, n, lambda i, s: s + jax.lax.dynamic_slice_in_dim(latv, i, 1,
                                                                axis=-1),
            jnp.zeros(latv.shape[:-1] + (1,), jnp.float32))
    p99 = nearest_rank_p99(lats, valid, xp)
    return xp.concatenate([makespan, p99, lat_sum, lat_max, n_valid],
                          axis=-1)


# ---------------------------------------------------------------------------
# Cross-client merge — the 2-D (trials × clients) grid's reduction twins
# ---------------------------------------------------------------------------


def tree_sum(x, axis: int = 0, xp=jnp):
    """Deterministic sum over ``axis``: the explicit pairwise halving tree
    of :func:`lane_sum`, generalized to any axis (zero-pad to the next
    power of two, then repeatedly fold the upper half onto the lower).
    Keeps the axis with size 1.  This is the WITHIN-BLOCK association of
    the cross-client merge: the 2-D grid kernel folds its ``client_tile``
    client sublanes through exactly these adds (DESIGN.md §11)."""
    c = x.shape[axis]
    size = _next_pow2(c)
    if size != c:
        pad = [(0, 0)] * x.ndim
        pad[axis] = (0, size - c)
        x = xp.pad(x, pad)
    lo = [slice(None)] * x.ndim
    hi = [slice(None)] * x.ndim
    while x.shape[axis] > 1:
        h = x.shape[axis] // 2
        lo[axis] = slice(0, h)
        hi[axis] = slice(h, None)
        x = x[tuple(lo)] + x[tuple(hi)]
    return x


def _mask_clients(x, client_valid, xp=jnp):
    """Zero the rows of phantom clients (leading client axis)."""
    cv = client_valid.reshape(client_valid.shape + (1,) * (x.ndim - 1))
    return xp.where(cv, x, xp.zeros_like(x))


def masked_client_sum(x, client_valid, client_tile: int, xp=jnp):
    """Cross-client masked sum over the LEADING client axis with the 2-D
    grid's float association: sequential (ascending) over
    ``ceil(C / client_tile)`` client blocks, each block folded by
    :func:`tree_sum` — one block per client tile of the 2-D grid — so
    the jax path, the oracle and the kernel path produce bit-identical
    merged floats (DESIGN.md §11).  ``client_valid``: (C,) bool —
    phantom clients (padded slices that scheduled nothing) contribute
    exact zeros.
    Returns ``x.shape[1:]``."""
    c = x.shape[0]
    xm = _mask_clients(x, client_valid, xp)
    n_blocks = -(-c // client_tile)
    if n_blocks * client_tile != c:
        pad = [(0, n_blocks * client_tile - c)] + [(0, 0)] * (x.ndim - 1)
        xm = xp.pad(xm, pad)
    out = None
    for b in range(n_blocks):
        blk = tree_sum(xm[b * client_tile:(b + 1) * client_tile], 0, xp)[0]
        out = blk if out is None else out + blk
    return out


def masked_client_mean(x, client_valid, client_tile: int, xp=jnp):
    """Masked cross-client mean: :func:`masked_client_sum` divided by the
    REAL client count (at least 1) — the "typical client's view"
    aggregate of the per_client contention model, shared verbatim by
    ``simulate``'s jax path and the grid kernel's merge
    (`ops.sched_stream_grid`)."""
    total = masked_client_sum(x, client_valid, client_tile, xp)
    dtype = total.dtype
    n_real = masked_client_sum(
        xp.ones(client_valid.shape, dtype), client_valid, client_tile, xp)
    return total / xp.maximum(n_real, xp.ones((), dtype))


def masked_client_max(x, client_valid, xp=jnp):
    """Masked cross-client max over the leading client axis (floored at 0
    — every merged metric is nonnegative).  ``max`` is order-free, so no
    association contract is needed."""
    return xp.max(_mask_clients(x, client_valid, xp), axis=0)


def client_stream_metrics(metrics, client_valid, client_tile: int, xp=jnp,
                          merged_lats=None, merged_valid=None):
    """Merge per-client stream-metric rows into the per-trial row of the
    2-D grid (DESIGN.md §11/§14).  ``metrics``:
    (C, >= N_METRICS) per-client rows (:func:`stream_metrics` layout);
    ``client_valid``: (C,) bool.  Returns (N_CMETRICS,) f32 in ``MET_*``
    + ``MET_N_CLIENTS`` order.

    ``merged_lats``/``merged_valid``: the (C, N) per-client grouped-step
    latency block and its validity — when given, the cross-client p99
    lane is :func:`nearest_rank_p99` over the flattened merged block
    (every reduction in it — counts of exact 0/1 floats, min/max — is
    order- and layout-insensitive, so ANY client/step ordering of the
    same multiset gives identical bits, DESIGN.md §14).  When omitted
    the lane is 0 — the pre-merged-block behaviour."""
    f32 = jnp.float32 if xp is jnp else np.float32
    metrics = metrics.astype(f32)
    mx = masked_client_max(metrics, client_valid, xp)
    sm = masked_client_sum(metrics, client_valid, client_tile, xp)
    n_real = masked_client_sum(xp.ones(client_valid.shape, f32),
                               client_valid, client_tile, xp)
    if merged_lats is None:
        p99 = xp.zeros((), f32)
    else:
        p99 = nearest_rank_p99(merged_lats.reshape(-1),
                               merged_valid.reshape(-1), xp)[0]
    return xp.stack([mx[MET_MAKESPAN], p99,
                     sm[MET_LAT_SUM], mx[MET_LAT_MAX], sm[MET_N_VALID],
                     n_real])


# ---------------------------------------------------------------------------
# Sharded cross-client merge — the device axis as one more association
# parameter (DESIGN.md §12)
# ---------------------------------------------------------------------------


def resolve_shard_width(n_clients: int, n_shards: int) -> int:
    """Clients per contiguous device shard of the client axis — the
    device-axis twin of :func:`resolve_client_tile`, shared by the
    sharded sweep dispatch (``parallel/sweep.py``) and the host oracle
    :func:`sharded_client_sum` so both layers pad and split the client
    axis identically (trailing shards fill up with phantom clients)."""
    if n_shards < 1:
        raise ValueError(f"n_shards={n_shards!r} must be >= 1")
    return -(-n_clients // n_shards)


def psum_tree(x, axis_name: str):
    """Deterministic cross-device sum over mesh axis ``axis_name``: the
    collective twin of :func:`tree_sum`.  ``all_gather`` stacks every
    device's pre-reduced partial in mesh-coordinate order, then the
    pinned halving tree folds the stack — NEVER ``jax.lax.psum``, whose
    reduction order is backend/topology-dependent.  Every device gathers
    identical operands and folds them through the same tree, so the
    result is replicated across the axis and bit-identical to the host
    oracle (:func:`sharded_client_sum`'s outer fold)."""
    g = jax.lax.all_gather(x, axis_name, axis=0)
    return tree_sum(g, axis=0)[0]


def sharded_client_sum(x, client_valid, client_tile, n_shards: int, xp=jnp):
    """Host oracle of the SHARDED cross-client merge (DESIGN.md §12):
    what ``parallel/sweep.py`` computes when the client axis is split
    over ``n_shards`` mesh devices.  Two association levels stack:

    1. pad the client axis with phantoms to ``n_shards`` equal
       contiguous shards of :func:`resolve_shard_width` clients and run
       :func:`masked_client_sum` WITHIN each shard — the per-device
       partial, with ``client_tile`` re-resolved against the shard
       width exactly as each device's 2-D grid kernel resolves it
       against its local client count;
    2. fold the per-shard partials with :func:`tree_sum` over the shard
       axis — what :func:`psum_tree` computes via ``all_gather``.

    ``n_shards == 1`` degenerates bit-exactly to ``masked_client_sum``
    with the no-mesh tile resolution.  ``client_tile`` may be ``None``
    (the package default), matching the config-level knob."""
    c = x.shape[0]
    w = resolve_shard_width(c, n_shards)
    c_pad = w * n_shards
    if c_pad != c:
        pad = [(0, c_pad - c)] + [(0, 0)] * (x.ndim - 1)
        x = xp.pad(x, pad)
        client_valid = xp.pad(client_valid, (0, c_pad - c))
    ct = resolve_client_tile(w, client_tile)
    parts = xp.stack([
        masked_client_sum(x[s * w:(s + 1) * w],
                          client_valid[s * w:(s + 1) * w], ct, xp)
        for s in range(n_shards)])
    return tree_sum(parts, 0, xp)[0]


def sharded_client_mean(x, client_valid, client_tile, n_shards: int, xp=jnp):
    """Sharded twin of :func:`masked_client_mean`: the shard-merged sum
    over the shard-merged real-client count (at least 1) — the division
    happens ONCE, globally, after the cross-device fold (a mean is not
    composable across devices; the kernel ships raw sums with
    ``merge_mean=False`` for exactly this reason)."""
    total = sharded_client_sum(x, client_valid, client_tile, n_shards, xp)
    dtype = total.dtype
    n_real = sharded_client_sum(xp.ones(client_valid.shape, dtype),
                                client_valid, client_tile, n_shards, xp)
    return total / xp.maximum(n_real, xp.ones((), dtype))
