"""Ahead-of-time compiles of the scheduler kernel for a described TPU v5e.

Every kernel form of the main path is lowered through Mosaic and
compiled at the paper's §4 widths (100 servers, 2,000 requests, window
100, 100 trials) for a v5e chip that is described, not attached: the
compiler refuses here what the chip would refuse (unaligned slices,
illegal block shapes, too much VMEM), at no chip time.  Nothing runs.

The topology is described inside a module fixture, never at import time:
only one process may load the TPU library, and each test worker imports
every test file.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.sched_select import ops

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
