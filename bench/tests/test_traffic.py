"""The traffic generator: seeded, the paper's jobs as the profile states
them, and file placement conserves each job's rate."""
import numpy as np
import pytest

from conftest import BENCH
from lib import harness, traffic

PROFILE = harness.load_json(BENCH / "profiles" / "filebench_iv.json")
#: (O, J, ticks): a fleet wider than the widest job, and one narrower
SIZES = [(32, 24, 700), (8, 16, 300)]


def fleet(size, seed):
    o, j, t = size
    return traffic.generate(PROFILE, o, j, 28.0, t, seed, "filebench_iv")


@pytest.mark.parametrize("size", SIZES)
def test_same_seed_same_arrays(size):
    seed = 2 ** 33 + 17           # wider than 32 bits
    a, b, c = fleet(size, seed), fleet(size, seed), fleet(size, 5)
    for x, y in zip(a, b):
        assert x.dtype == y.dtype and np.array_equal(x, y)
    assert not np.array_equal(a.trace, c.trace)
    # a seed moves each copy along its timeline; the jobs stay the same
    for x, y in zip(a[:1] + a[2:], c[:1] + c[2:]):
        assert np.array_equal(x, y)


@pytest.mark.parametrize("size", SIZES)
def test_striping_conserves_each_jobs_demand(size):
    f = fleet(size, 11)
    rates = traffic.expand(f)
    np.testing.assert_allclose(rates.sum(axis=1, dtype=np.float64),
                               f.trace.astype(np.float64), rtol=1e-6,
                               atol=1e-7)
    np.testing.assert_allclose(f.weights.sum(axis=0), 1.0, rtol=1e-6)
    bounded = np.isfinite(f.volume).all(axis=0)
    total = np.where(f.weights > 0, f.volume, 0.0).sum(axis=0)
    # a bounded job's volume is split over its files, and its clipped
    # trace never issues past it
    assert (f.trace.sum(axis=0, dtype=np.float64)[bounded]
            <= total[bounded] * (1 + 1e-5)).all()


def test_jobs_are_the_papers_jobs():
    f = fleet(SIZES[0], 3)
    exps = PROFILE["experiments"]
    for j in range(SIZES[0][1]):
        job = exps[(j // 4) % len(exps)]["jobs"][j % 4]
        assert f.nodes[j] == job["nodes"]
        files = f.weights[:, j] > 0
        assert files.sum() == job["processes"]
        # one file per target, each with its process's in-flight cap
        assert (f.backlog[files, j] == PROFILE["in_flight_per_process"]).all()
        assert (f.backlog[~files, j] == 0).all()
        want = np.inf if job["volume_rpcs"] is None else job["volume_rpcs"]
        np.testing.assert_allclose(f.volume[files, j].sum(), want)
    # IV-D's continuous writers issue 40 RPCs a tick until their volume
    assert f.trace[0, 0] == 40.0
    # IV-F's first job: a 30-RPC burst every 10 ticks, 3 RPCs a tick on
    # average, plus 20 a tick once its second process has started
    x = f.trace[:, 8]
    assert set(np.unique(x).tolist()) <= {0.0, 20.0, 30.0, 50.0}
    assert (x >= 30).sum() == x.size // 10
    assert (f.capacity == 28.0).all()


def test_rows_of_expand_are_its_slices():
    f = fleet(SIZES[0], 3)
    rows = np.array([1, 5])
    np.testing.assert_array_equal(traffic.expand(f, rows),
                                  traffic.expand(f)[:, rows])


def test_stripe_sets_are_consecutive_targets():
    w = traffic.file_weights(np.array([1, 2, 3]), 3)
    # files 0 | 1, 2 | 3, 4, 5 on targets 0 | 1, 2 | 0, 1, 2
    assert w[:, 0].tolist() == [1.0, 0.0, 0.0]
    np.testing.assert_allclose(w[:, 1], [0.0, 0.5, 0.5])
    np.testing.assert_allclose(w[:, 2], [1 / 3] * 3, rtol=1e-6)
    # more files than targets: the files wrap, and each target's share
    # counts the files it holds
    w = traffic.file_weights(np.array([5]), 2)
    np.testing.assert_allclose(w[:, 0], [0.6, 0.4], rtol=1e-6)
