"""The harness on the CPU: no chip, no result; and a whole run at a tiny
size with the timed path sound, replaced by the lower-precision control,
or broken underneath, deciding ``correct``."""

import dataclasses
import io
import json
import weakref

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import reference, run, spec

CELL = "paper4_shared_log.transient_ect"
TINY = dict(n_servers=16, n_requests=120, n_trials=4, window_size=20,
            n_clients=12)


def tiny_cell(name=CELL, **sim):
    cell = spec.load_cell(name)
    config = dict(cell.config, sim={**cell.config["sim"], **TINY, **sim})
    return dataclasses.replace(cell, config=config)


def drive(cell, sweep, seconds=0.3, seed=2**31 + 11):
    """A run of ``cell`` with ``sweep`` as the timed path, past the
    harness's look for a chip."""
    dev = {"platform": "cpu", "kind": "cpu", "count": 1,
           "devices": jax.devices()[:1]}
    err = io.StringIO()
    result = run.run_cell(cell, seed, seconds, False, sweep, jax, dev,
                          peaks={}, t_start=0.0, err=err)
    json.dumps(result)
    return result


def reference_sweep(cell, dtype=jnp.float32, fault=None):
    """The reference, running free, in the program's place."""
    sh = reference.shape_from(cell.config, cell.traffic)
    fn = reference.reference_fn(sh, forced=False, dtype=dtype)
    state = {}

    def sweep(key):
        out = dict(fn(key))
        out.pop("mine")
        return fault(out, state) if fault else out
    return sweep


def test_cpu_only_device_exits_nonzero_with_no_result(capsys, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", run.CACHE_DIR)
    rc = run.main(["--workload", CELL, "--seed", "1", "--seconds", "1"])
    out, err = capsys.readouterr()
    assert rc != 0
    assert out.strip() == ""
    assert "no TPU" in err


def test_unknown_cell_exits_nonzero(capsys):
    assert run.main(["--workload", "nope.none", "--seed", "1",
                     "--seconds", "1"]) != 0
    assert capsys.readouterr().out.strip() == ""


def test_reference_in_the_program_place_is_correct():
    cell = tiny_cell()
    res = drive(cell, reference_sweep(cell))
    assert res["correct"], res
    assert res["compared"]["wrong_answers"]["value"] == 0.0
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert set(res["metrics"]) == {"decisions_per_s", "sweep_ms_p95",
                                   "setup_s"}
    assert list(res)[-1] == "compared"


def test_control_in_bfloat16_is_not_correct():
    cell = tiny_cell()
    res = drive(cell, reference_sweep(cell, dtype=jnp.bfloat16))
    assert not res["correct"], res


def _unchanged(out, state):
    """A sweep that returns the state it had: the first sweep's outputs
    again."""
    return state.setdefault("first", out)


def _half_batch(out, state):
    """Half of the trials left out (zeros)."""
    t = out["chosen"].shape[0]
    return {k: v.at[t // 2:].set(jnp.zeros((), v.dtype))
            if v.ndim and v.shape[0] == t else v for k, v in out.items()}


def _answer_altered(out, state):
    """One request's server altered where it is produced."""
    m = out["n_assigned"].shape[1]
    return dict(out, chosen=out["chosen"].at[0, 0].set(
        (out["chosen"][0, 0] + 1) % m))


def _exchange_left_out(out, state):
    """The cross-client merge without the other chip's half: the mean
    snapshot over the clients of one chip only."""
    return dict(out, window_loads=out["window_loads"] * 0.5)


@pytest.mark.parametrize("fault,cell_name", [
    (_unchanged, CELL), (_half_batch, CELL), (_answer_altered, CELL),
    (_exchange_left_out, "paper4_per_client.transient_ect"),
    (_exchange_left_out, "paper4_per_client_2x2.transient_ect")])
def test_broken_timed_path_is_not_correct(fault, cell_name):
    cell = tiny_cell(cell_name)
    res = drive(cell, reference_sweep(cell, fault=fault), seconds=0.2)
    assert not res["correct"], res


def test_program_at_tiny_size_is_correct():
    cell = tiny_cell()
    res = drive(cell, run.program_sweep(cell), seconds=0.3)
    assert res["correct"], res


def test_the_window_holds_only_the_sampled_outputs():
    cell = tiny_cell()
    inner = reference_sweep(cell)
    live, most = [0], [0]

    def sweep(key):
        out = dict(inner(key))
        out["chosen"] = np.asarray(out["chosen"])
        live[0] += 1
        most[0] = max(most[0], live[0])
        weakref.finalize(out["chosen"], lambda: live.__setitem__(
            0, live[0] - 1))
        return out
    res = drive(cell, sweep, seconds=0.5)
    assert res["correct"], res
    assert res["attempted"] > run.N_CHECKED + run.N_WARMUP
    # the sample, the sweep in flight and the one just replaced at most
    assert most[0] <= run.N_CHECKED + 2
