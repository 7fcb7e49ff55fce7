"""Layer 2: jaxpr verification of the fused surface.

The AST layer sees call sites; it cannot see a ``jnp.sort`` hiding two
helpers deep.  This layer traces the real kernel entry points
(`kernel.sched_stream_call`, `kernel.sched_stream_grid_call`, and
`engine.run_stream_batch`) at tiny abstract shapes, finds every
``pallas_call`` equation in the closed jaxpr, and walks the *inner*
(fused) jaxprs — plus all their scan/cond/pjit sub-jaxprs — asserting:

* **CJ-SORT** — no ``sort`` primitive.  The §10/§13 contract lowers all
  fused ordering through `rank_desc`/the bitonic network, which emit
  compares and selects, never ``sort_p``.
* **CJ-SUM** — no float ``reduce_sum``/``cumsum`` whose operand is not a
  masked select (``select_n``) or integer/bool.  The pinned
  `lane_sum`/`tree_sum` halving trees lower to explicit ``add`` chains
  and are invisible here by construction — so any ``reduce_sum`` that
  shows up was NOT routed through them.
* **CJ-RNG** — no RNG primitives (threefry/random_bits/…).  Fused
  randomness is the shared LCG: integer mul/add/and.

The *outer* jaxpr is deliberately out of scope: the engine keeps
backend argsort for step grouping (§10) and jax.random for seeding, so
only what lowers into a pallas body is held to the fused rules.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, List, Optional, Sequence, Tuple

from repro.contractcheck.config import CheckConfig, load_config
from repro.contractcheck.rules import Finding, apply_severity

RNG_SUBSTRINGS = ("threefry", "random_bits", "random_seed", "random_wrap",
                  "random_fold_in", "random_gamma", "rng_bit_generator")
ACCUM_PRIMS = {"reduce_sum", "cumsum"}
# producers whose output is a masked select or otherwise
# association-free, blessing a downstream reduce_sum
MASK_PRODUCERS = {"select_n"}


def _subjaxprs(params: dict):
    """Yield every Jaxpr/ClosedJaxpr nested in an eqn's params
    (duck-typed — the concrete classes move between jax versions)."""
    for v in params.values():
        vals = v if isinstance(v, (tuple, list)) else (v,)
        for item in vals:
            if hasattr(item, "eqns") or hasattr(item, "jaxpr"):
                yield item


def _as_jaxpr(j):
    return j.jaxpr if hasattr(j, "jaxpr") else j


def _walk_eqns(jaxpr, into_pallas: bool = True):
    """Yield (jaxpr_level, eqn) over a jaxpr and its sub-jaxprs."""
    jaxpr = _as_jaxpr(jaxpr)
    for eqn in jaxpr.eqns:
        yield jaxpr, eqn
        if eqn.primitive.name == "pallas_call" and not into_pallas:
            continue
        for sub in _subjaxprs(eqn.params):
            yield from _walk_eqns(sub, into_pallas)


def find_pallas_jaxprs(closed) -> List[Tuple[str, Any]]:
    """Every pallas_call's inner jaxpr in a traced computation (the
    search recurses through pjit/scan wrappers)."""
    out = []
    for _, eqn in _walk_eqns(closed, into_pallas=False):
        if eqn.primitive.name == "pallas_call":
            name = eqn.params.get("name", "pallas_call")
            out.append((str(name), eqn.params["jaxpr"]))
    return out


def _eqn_location(eqn, fallback: str) -> Tuple[str, int]:
    """Best-effort user file:line from the eqn's source info."""
    try:
        frames = eqn.source_info.traceback.frames
        for fr in frames:
            fname = getattr(fr, "file_name", "")
            if "/repro/" in fname.replace("\\", "/"):
                return fname, int(getattr(fr, "line_num", 0))
    except Exception:
        pass
    return fallback, 0


def _is_integral(aval) -> bool:
    import numpy as np
    dt = getattr(aval, "dtype", None)
    return dt is not None and (np.issubdtype(dt, np.integer)
                               or np.issubdtype(dt, np.bool_))


# wrappers that move data without combining it — resolve through them
# when hunting for the semantic producer of a reduce operand
_TRANSPARENT = {"reshape", "broadcast_in_dim", "squeeze", "transpose",
                "slice", "rev", "copy"}
# call-like primitives whose result is really produced by an inner jaxpr
_CALL_LIKE = {"jit", "pjit", "closed_call", "core_call", "custom_jvp_call",
              "custom_vjp_call", "custom_vjp_call_jaxpr", "remat"}


def _producer_map(level):
    producers = {}
    for eqn in _as_jaxpr(level).eqns:
        for ov in eqn.outvars:
            producers[id(ov)] = eqn
    return producers


def _effective_producer(level, var, depth: int = 0):
    """(level, eqn) that semantically produced ``var``, looking through
    call-like wrappers (jnp.where traces as a jit call around select_n) and
    pure data movement.  (None, None) when untraceable."""
    if depth > 16:
        return None, None
    prod = _producer_map(level).get(id(var))
    if prod is None:
        return None, None
    name = prod.primitive.name
    if name in _CALL_LIKE:
        inner = None
        for sub in _subjaxprs(prod.params):
            inner = sub
            break
        if inner is None:
            return level, prod
        ij = _as_jaxpr(inner)
        for idx, ov in enumerate(prod.outvars):
            if ov is var:
                out = ij.outvars[idx]
                for iidx, iv in enumerate(ij.invars):
                    if iv is out:     # pass-through: follow the call arg
                        return _effective_producer(
                            level, prod.invars[iidx], depth + 1)
                return _effective_producer(inner, out, depth + 1)
        return level, prod
    if name in _TRANSPARENT:
        return _effective_producer(level, prod.invars[0], depth + 1)
    return level, prod


def _operand_blessed(level, var, depth: int = 0) -> bool:
    """True when a reduce operand is association-free: integer/bool, a
    masked ``select_n``, or an element-type cast of either (``jnp.where
    (m, 1.0, 0.0)`` lowers to select_n → weak-f32 → convert)."""
    if depth > 8:
        return False
    if _is_integral(getattr(var, "aval", None)):
        return True
    plevel, prod = _effective_producer(level, var)
    if prod is None:
        return False
    if prod.primitive.name in MASK_PRODUCERS:
        return True
    if prod.primitive.name == "convert_element_type":
        return _operand_blessed(plevel, prod.invars[0], depth + 1)
    return False


def check_fused_jaxpr(jaxpr, label: str) -> List[Finding]:
    """Apply the CJ-* rules to one fused (inside-pallas) jaxpr."""
    findings: List[Finding] = []

    def emit(rule_id, eqn, msg):
        path, line = _eqn_location(eqn, f"<jaxpr:{label}>")
        findings.append(Finding(rule_id, path, line,
                                f"[{label}] {msg}", func=label))

    for level, eqn in _walk_eqns(jaxpr):
        name = eqn.primitive.name
        if name == "sort":
            emit("CJ-SORT", eqn,
                 "sort primitive in a fused body — §10 lowers fused "
                 "ordering through rank_desc/bitonic only")
        if any(s in name for s in RNG_SUBSTRINGS):
            emit("CJ-RNG", eqn,
                 f"RNG primitive {name} in a fused body — fused "
                 "randomness is the shared LCG (§9)")
        if name in ACCUM_PRIMS:
            if _operand_blessed(level, eqn.invars[0]):
                continue
            emit("CJ-SUM", eqn,
                 f"raw float {name} whose operand is not a masked "
                 "select/integer — route through lane_sum/tree_sum (§9)")
    return findings


def check_callable(fn: Callable, args: Sequence[Any],
                   label: str = "fn",
                   fused_whole: bool = False) -> List[Finding]:
    """Trace ``fn(*args)`` and check its fused jaxprs.  With
    ``fused_whole`` the entire jaxpr is held to the fused rules (for toy
    bodies in tests); otherwise only pallas inner jaxprs are."""
    import jax
    closed = jax.make_jaxpr(fn)(*args)
    if fused_whole:
        return check_fused_jaxpr(closed, label)
    findings: List[Finding] = []
    for name, inner in find_pallas_jaxprs(closed):
        findings.extend(check_fused_jaxpr(inner, f"{label}:{name}"))
    return findings


# -- tracing the real kernel surface ----------------------------------------

def _tiny_operands(two_d: bool):
    import jax.numpy as jnp
    m, m_pad = 3, 128
    window, n_win = 2, 2
    n = window * n_win
    t, c = 2, 2
    lead = (t, c) if two_d else (t,)
    obj = jnp.zeros(lead + (n,), jnp.int32)
    lens = jnp.ones(lead + (n,), jnp.float32)
    val = jnp.ones(lead + (n,), jnp.int32)
    from repro.core.policy_core import init_table
    table = jnp.broadcast_to(
        jnp.pad(init_table(m), ((0, 0), (0, m_pad - m))),
        lead + (4, m_pad))
    seeds = jnp.ones(lead + (1,), jnp.uint32) if not two_d else \
        jnp.ones(lead, jnp.uint32)
    rates = jnp.ones((t, n_win, m_pad) if two_d else lead + (n_win, m_pad),
                     jnp.float32)
    kw = dict(n_servers=m, window_size=window, threshold=0.0, lam=32.0,
              alpha=0.25, window_dt=0.0, observe=True, renorm=True)
    return (obj, lens, val, table, seeds, rates), kw


def trace_kernel_calls(policies: Sequence[str]) -> List[Finding]:
    """Check the 1-D trial-grid and 2-D grid kernel bodies for each
    policy (tracing only — nothing executes)."""
    from repro.kernels.sched_select import kernel
    findings: List[Finding] = []
    for policy in policies:
        (obj, lens, val, table, seeds, rates), kw = _tiny_operands(False)
        fn = functools.partial(kernel.sched_stream_call, policy=policy,
                               interpret=True, **kw)
        findings.extend(check_callable(
            fn, (obj, lens, val, table, seeds, rates),
            label=f"sched_stream_call[{policy}]"))
        (obj, lens, val, table, seeds, rates), kw = _tiny_operands(True)
        fn = functools.partial(kernel.sched_stream_grid_call, policy=policy,
                               interpret=True, trial_tile=1, client_tile=1,
                               **kw)
        findings.extend(check_callable(
            fn, (obj, lens, val, table, seeds, rates),
            label=f"sched_stream_grid_call[{policy}]"))
    return findings


def trace_run_stream_batch(policy: str = "ect") -> List[Finding]:
    """Check the full `engine.run_stream_batch` dispatch — padding,
    prep, kernel, bookkeeping — as the contract's integration point."""
    import jax
    import jax.numpy as jnp
    from repro.core import engine
    from repro.core.policies import PolicyConfig
    from repro.core.statlog import LogConfig, init_state

    m, t, window, n_win = 3, 2, 2, 2
    n = window * n_win
    state = init_state(LogConfig(n_servers=m))
    states = jax.tree.map(
        lambda a: jnp.broadcast_to(a, (t,) + a.shape), state)
    works = engine.Workload(
        object_ids=jnp.zeros((t, n), jnp.int32),
        lengths=jnp.ones((t, n), jnp.float32),
        valid=jnp.ones((t, n), bool))
    keys = jax.random.split(jax.random.PRNGKey(0), t)

    def fn(states, works, keys):
        return engine.run_stream_batch(
            states, works, keys, policy=PolicyConfig(name=policy),
            log_cfg=LogConfig(n_servers=m), window_size=window,
            backend="kernel")

    return check_callable(fn, (states, works, keys),
                          label=f"run_stream_batch[{policy}]")


def check_kernels(cfg: Optional[CheckConfig] = None) -> List[Finding]:
    """The jaxpr shard of a full checker run."""
    cfg = cfg or load_config()
    findings = trace_kernel_calls(cfg.jaxpr_policies)
    findings.extend(trace_run_stream_batch(cfg.jaxpr_policies[0]))
    return apply_severity(findings, cfg.severity)
