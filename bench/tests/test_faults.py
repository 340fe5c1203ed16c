"""A whole run, past the look for a chip, with the timed path broken
underneath: ``correct`` must come out false for each fault a one-chip cell
can have.  (The exchange between chips does not exist on one chip.)"""
import time

import jax
import jax.numpy as jnp
import pytest

from lib import harness

CELLS = ["lustre248x4096.online_filebench",
         "lustre248x4096.replay_filebench",
         "lustre248x4096.sweep_filebench"]


def unchanged(real):
    """The step returns the state it was given."""
    def step(*args, **kw):
        _, out = real(*args, **kw)
        return args[5], out
    return step


def half_rows(real):
    """Only the first half of the targets is stepped; the rest keep their
    state and report nothing."""
    def step(*args, **kw):
        new, out = real(*args, **kw)
        old = args[5]
        n = old.queue.shape[0]
        keep = jnp.arange(n) < n // 2

        def pick(a, b):
            if a.ndim and a.shape[0] == n:
                return jnp.where(keep.reshape((n,) + (1,) * (a.ndim - 1)),
                                 a, b)
            return a
        new = jax.tree.map(pick, new, old)
        if out is not None:
            out = jax.tree.map(lambda x: jnp.where(keep[:, None], x, 0.0),
                               out)
        return new, out
    return step


def token(real):
    """Every window, each target's allocation gives one job one token
    more than the policy produced."""
    def step(*args, **kw):
        new, out = real(*args, **kw)
        n_jobs = new.alloc.shape[-1]
        extra = (jnp.arange(n_jobs) == new.window % n_jobs).astype(
            new.alloc.dtype)
        return new._replace(alloc=new.alloc + extra), out
    return step


@pytest.mark.parametrize("fault", [unchanged, half_rows, token])
@pytest.mark.parametrize("name", CELLS)
def test_broken_timed_path_is_not_correct(small, monkeypatch, name, fault):
    _, resolve = small
    harness.import_program()
    from repro.storage import service, simulator
    monkeypatch.setattr(service, "window_step",
                        fault(simulator.window_step))
    monkeypatch.setattr(simulator, "window_step",
                        fault(simulator.window_step))
    jax.clear_caches()
    try:
        result = harness.run_cell(resolve(name), 2 ** 31 + 9, 0.5, False,
                                  time.perf_counter(), require_tpu=False)
    finally:
        jax.clear_caches()
    assert result["attempted"] > 0
    assert result["correct"] is False, result["checks"]


def test_sound_run_is_correct(small):
    _, resolve = small
    result = harness.run_cell(resolve(CELLS[0]), 2 ** 31 + 9, 0.5, False,
                              time.perf_counter(), require_tpu=False)
    assert result["correct"] is True, result["checks"]
    assert list(result)[-1] == "checks"
