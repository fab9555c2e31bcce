"""The benchmark's generators: the same seed gives the same inputs, another
seed other ones, and an open-loop mix gives every seed the same work."""
import jax
import numpy as np
import pytest

from bench import generators as g

BIG = 2**33 + 12345          # seeds may exceed 32 bits


def test_geometric_lengths():
    a = g.geometric_lengths(BIG, 500, 500)
    np.testing.assert_array_equal(a, g.geometric_lengths(BIG, 500, 500))
    assert not np.array_equal(a, g.geometric_lengths(BIG + 1, 500, 500))
    assert a.min() >= 2 and a.max() <= 500


def test_fbm_paths_device():
    f = jax.jit(lambda k: g.fbm_paths_device(k, 8, 32, 3),)
    X, H = f(g.seed_key(BIG))
    X2, H2 = f(g.seed_key(BIG))
    X3, _ = f(g.seed_key(BIG + 1))
    np.testing.assert_array_equal(X, X2)
    np.testing.assert_array_equal(H, H2)
    assert not np.array_equal(X, X3)
    assert X.shape == (8, 33, 3) and np.all(np.isfinite(X))
    assert np.all((H >= 0.25) & (H <= 0.75))


def test_fbm_device_matches_the_host_construction():
    """Same H and normals: the device draw is the host's Cholesky path."""
    key = g.seed_key(7)
    X, H = g.fbm_paths_device(key, 3, 24, 2)
    z = jax.random.normal(jax.random.split(key)[1], (3, 24, 2))
    t = np.linspace(1 / 24, 1, 24)
    for p in range(3):
        h = float(H[p])
        cov = 0.5 * (t[:, None] ** (2 * h) + t[None] ** (2 * h)
                     - np.abs(t[:, None] - t[None]) ** (2 * h))
        L = np.linalg.cholesky(cov + 1e-6 * np.eye(24))
        np.testing.assert_allclose(X[p, 1:], L @ np.asarray(z[p]),
                                   rtol=1e-3, atol=1e-4)


def test_seed_key_uses_every_bit():
    keys = [g.seed_key(s) for s in (5, 5 + 2**32, 5 + 2**33)]
    flat = {tuple(np.asarray(jax.random.key_data(k)).ravel()) for k in keys}
    assert len(flat) == 3


def test_walk_increments():
    a = g.walk_increments(g.seed_key(BIG), (2, 100, 3))
    np.testing.assert_array_equal(a, g.walk_increments(g.seed_key(BIG),
                                                       (2, 100, 3)))
    assert not np.array_equal(a, g.walk_increments(g.seed_key(BIG + 1),
                                                   (2, 100, 3)))
    total = np.asarray(g.walk_increments(g.seed_key(3), (256, 100, 3))).sum(1)
    assert abs(float(np.var(total)) - 1.0) < 0.2       # unit total variance


@pytest.mark.parametrize("rate,seconds", [(300.0, 20.0), (50.0, 3.0)])
def test_open_loop_schedule_same_work_other_order(rate, seconds):
    due, lengths = g.open_loop_schedule(BIG, rate, seconds, 500, 2, 0.25)
    due2, lengths2 = g.open_loop_schedule(BIG, rate, seconds, 500, 2, 0.25)
    due3, lengths3 = g.open_loop_schedule(BIG + 1, rate, seconds, 500, 2,
                                          0.25)
    np.testing.assert_array_equal(due, due2)
    np.testing.assert_array_equal(lengths, lengths2)
    assert not np.array_equal(lengths, lengths3)
    assert not np.array_equal(due, due3)
    np.testing.assert_array_equal(np.sort(lengths), np.sort(lengths3))
    np.testing.assert_allclose(np.sort(np.diff(due, prepend=0)),
                               np.sort(np.diff(due3, prepend=0)), rtol=1e-6)
    assert len(due) == round(rate * seconds)
    assert np.all(np.diff(due) > 0) and abs(due[-1] - seconds) < 1e-9
    assert lengths.min() >= 2 and lengths.max() <= 500


def test_request_paths():
    lengths = np.array([3, 1, 7])
    a = g.request_paths(BIG, lengths, 4)
    b = g.request_paths(BIG, lengths, 4)
    c = g.request_paths(BIG + 1, lengths, 4)
    assert [p.shape for p in a] == [(4, 4), (2, 4), (8, 4)]
    for x, y, z in zip(a, b, c):
        np.testing.assert_array_equal(x, y)
        assert not np.array_equal(x, z)
        assert np.all(x[0] == 0)
