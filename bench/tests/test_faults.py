"""`correct` comes out false when the timed path is broken underneath.

Each test runs the whole harness (set-up, window, drain, the reference
comparison) on the CPU at a tiny size with one fault planted in the
verify step program's outputs, and checks the run is judged incorrect by
the number meant to catch it; the sound run is judged correct."""
import time

import jax
import jax.numpy as jnp

from conftest import tiny_spec
from harness import cell

SEED = 20240611


def _run(fault, **kw):
    spec = tiny_spec(slots=4, **kw)
    spec["cell"]["audit"] = {"rounds": 10, "min_rounds": 3}
    res, rows = cell.run(spec, SEED, 2.0, False, time.perf_counter(),
                         jax.devices(), fault=fault)
    return res, {n: (v, ok) for n, v, _, ok in rows}


def test_sound_run_is_correct():
    res, rows = _run(None)
    assert res["correct"], rows


def test_state_left_unchanged():
    """The verify step hands back the cache it was given."""
    def fault(cache_in, out):
        res, p, _, traj = out
        return res, p, cache_in, traj
    res, rows = _run(fault)
    assert not res["correct"]
    assert not rows["target_gap"][1], rows


def test_half_the_batch_left_out():
    """Rows in the upper half of the batch get the lower half's outputs."""
    def fault(cache_in, out):
        res, p, cache, traj = out
        h = -(-p.shape[0] // 2)
        src = jnp.arange(p.shape[0]) % h
        res = type(res)(*(a[src] for a in res))
        return res, p[src], cache, traj
    res, rows = _run(fault)
    assert not res["correct"]
    assert not (rows["target_gap"][1] and rows["emission_faults"][1]), rows


def test_token_altered_where_produced():
    """The verify step's new token is shifted by one."""
    def fault(cache_in, out):
        res, p, cache, traj = out
        res = res._replace(new_token=(res.new_token + 1) % p.shape[-1])
        return res, p, cache, traj
    res, rows = _run(fault)
    assert not res["correct"]
    assert not rows["emission_faults"][1], rows
