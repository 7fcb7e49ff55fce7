"""Ahead-of-time compiles of the scheduler kernel for a described TPU v5e.

Every kernel form of the main path is lowered through Mosaic and
compiled at the paper's §4 widths (100 servers, 2,000 requests, window
100, 100 trials) for a v5e chip that is described, not attached: the
compiler refuses here what the chip would refuse (unaligned slices,
illegal block shapes, too much VMEM), at no chip time.  Nothing runs.
The whole sweep program, ``simulate.run_trials``, is compiled too, at a
small shape: each of its ops must sit in a stage scope, and the scopes
must leave the program and the kernel's name as they were.

The topology is described inside a module fixture, never at import time:
only one process may load the TPU library, and each test worker imports
every test file.
"""

from __future__ import annotations

import contextlib
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from bench import devtrace, stagetrace
from repro.core import simulate
from repro.core.policies import PolicyConfig
from repro.kernels.sched_select import ops
from repro.tune import profile

M, T, R, WINDOW = 100, 100, 2000, 100


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-chip compile cannot be read back from the persistent
    # cache without a chip: keep it out of the cache
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


def _compile(fn, shapes, sharding, **kw):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    compiled = fn.lower(*args, interpret=False, **kw).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _common(policy):
    return dict(n_servers=M, threshold=0.05 if policy == "ect" else 5.0,
                lam=100.0, alpha=0.25, window_dt=0.5, policy=policy,
                observe=True, renorm=True)


@pytest.mark.parametrize("policy", ["ect", "trh", "mlml", "nltr"])
def test_trial_grid_compiles_for_v5e(one_chip, policy):
    n_win = R // WINDOW
    shapes = [((T, R), jnp.int32), ((T, R), jnp.float32), ((T, R), jnp.bool_),
              ((T, 4, M), jnp.float32), ((T,), jnp.uint32),
              ((T, n_win, M), jnp.float32)]
    _compile(ops.sched_stream_batch, shapes, one_chip, window_size=WINDOW,
             **_common(policy))


@pytest.mark.parametrize("n_clients", [4, 64, 200])
def test_client_grid_compiles_for_v5e(one_chip, n_clients):
    # the per_client split of simulate._client_split_shape
    per = -(-R // n_clients)
    win = min(WINDOW, per)
    n_win = -(-per // win)
    n = n_win * win
    c = n_clients
    shapes = [((T, c, n), jnp.int32), ((T, c, n), jnp.float32),
              ((T, c, n), jnp.bool_), ((T, c, 4, M), jnp.float32),
              ((T, c), jnp.uint32), ((T, n_win, M), jnp.float32)]
    _compile(ops.sched_stream_grid, shapes, one_chip, window_size=win,
             **_common("ect"))


# --- the stage scopes of the whole sweep program (DESIGN.md §16) -------

# no computation: exempt wherever they come from
EXEMPT = ("parameter", "constant", "tuple", "get-tuple-element", "bitcast")
# what XLA adds on its own, with no op_name from the program: broadcasts
# of constants, iotas, layout reshapes and the copies of memory placement
XLA_OWN = ("broadcast", "iota", "reshape", "copy", "copy-start",
           "copy-done")
_ENTRY = re.compile(r"^ENTRY %[\w.\-]+ ")
_INSTR = re.compile(r"^\s*(?:ROOT )?%([\w.\-]+) = .*?\s([a-z][a-z\-]*)\(")


def _entry_ops(text):
    """``(name, opcode)`` of every instruction of the entry computation:
    the ops the device runs (the sweep program has no loop at its top)."""
    out, inside = [], False
    for line in text.splitlines():
        if _ENTRY.match(line):
            inside = True
        elif inside and line.startswith("}"):
            break
        elif inside:
            m = _INSTR.match(line)
            if m:
                out.append(m.groups())
    assert out
    return out


def _run_trials_text(sharding, client_model):
    cfg = simulate.SimConfig(
        n_servers=16, n_requests=64, n_trials=8, window_size=16,
        n_clients=4, client_model=client_model, backend="kernel",
        straggler_frac=0.1,
        scenario=simulate.ScenarioConfig(name="transient"))
    pol = PolicyConfig(name="ect", threshold=0.05, rng="lcg")
    key = jax.ShapeDtypeStruct((), jax.random.key(0).dtype,
                               sharding=sharding)
    return simulate.run_trials.lower(
        key, cfg, pol, simulate.default_log_cfg(cfg)).compile().as_text()


@pytest.fixture
def compiled_sweep(one_chip, monkeypatch):
    """``client_model -> compiled text`` of ``simulate.run_trials`` for
    the described chip, its kernel compiled by Mosaic (no interpret)."""
    monkeypatch.setattr(ops, "_auto_interpret",
                        lambda i: False if i is None else i)
    jax.clear_caches()
    yield lambda model: _run_trials_text(one_chip, model)
    jax.clear_caches()


def test_bench_keeps_the_program_stage_names():
    assert stagetrace.STAGES == profile.STAGES
    assert stagetrace.PARENTS == profile.PARENTS


@pytest.mark.parametrize("client_model", ["shared_log", "per_client"])
def test_every_op_of_the_sweep_sits_in_a_stage(compiled_sweep, client_model):
    text = compiled_sweep(client_model)
    module, paths = stagetrace.program_op_names(text)
    assert module == "jit_run_trials"
    instrs = _entry_ops(text)
    stages = {stagetrace.stage_of(paths.get(n, "")) for n, _ in instrs}
    # shared_log has no cross-client merge
    want = set(profile.STAGES) - ({"merge"} if client_model == "shared_log"
                                  else set())
    assert want <= stages
    kernels = [n for n, opc in instrs if opc == "custom-call"
               and paths.get(n, "").endswith("/pallas_call")]
    assert len(kernels) == 1
    assert devtrace.KERNEL.search(kernels[0]), kernels[0]
    path = paths[kernels[0]]
    assert "/sched/kernel/" in path and stagetrace.stage_of(path) == "kernel"
    unscoped = [(n, opc, paths.get(n)) for n, opc in instrs
                if stagetrace.stage_of(paths.get(n, "")) == "unscoped"
                and opc not in EXEMPT
                and not (opc in XLA_OWN and not paths.get(n))]
    assert not unscoped


def test_scopes_leave_the_compiled_program_as_it_was(compiled_sweep,
                                                     monkeypatch):
    scoped = sorted(_entry_ops(compiled_sweep("per_client")))
    monkeypatch.setattr(profile, "stage",
                        lambda name: contextlib.nullcontext())
    jax.clear_caches()
    assert sorted(_entry_ops(compiled_sweep("per_client"))) == scoped
