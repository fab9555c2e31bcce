"""Windowed-signature tests (paper §5): one-call batch == per-window loop."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

import repro.core as C
from repro.core import (dyadic_windows, expanding_windows, sliding_windows,
                        windowed_projection, windowed_signature,
                        windowed_signature_chen)
from repro.core.words import make_plan
from tests.conftest import make_path


def _oracle(path, windows, N):
    return np.stack([np.asarray(C.signature(path[:, l:r + 1], N))
                     for l, r in windows], axis=1)  # noqa: E741


def test_matches_per_window_oracle(rng):
    path = make_path(rng, 3, 20, 3)
    windows = np.asarray([[0, 20], [0, 5], [5, 12], [11, 20], [7, 8]],
                         np.int32)
    out = windowed_signature(jnp.asarray(path), windows, 3)
    np.testing.assert_allclose(out, _oracle(path, windows, 3),
                               rtol=1e-4, atol=1e-5)


def test_chen_route_agrees(rng):
    path = make_path(rng, 2, 24, 3)
    windows = sliding_windows(24, 8, stride=4)
    a = windowed_signature(jnp.asarray(path), windows, 3)
    b = windowed_signature_chen(jnp.asarray(path), windows, 3)
    np.testing.assert_allclose(a, b, rtol=1e-3, atol=1e-4)


def test_expanding_windows_equal_stream(rng):
    path = jnp.asarray(make_path(rng, 2, 10, 2))
    wins = expanding_windows(10)
    ws = windowed_signature(path, wins, 3)
    stream = C.signature(path, 3, stream=True)
    np.testing.assert_allclose(ws, stream, rtol=1e-4, atol=1e-5)


def test_windowed_projection_subset(rng):
    d = 3
    path = jnp.asarray(make_path(rng, 2, 16, d))
    windows = np.asarray([[0, 8], [4, 16]], np.int32)
    words = [(0,), (2, 1), (1, 1, 0)]
    plan = make_plan(words, d)
    proj = windowed_projection(path, windows, plan)
    full = windowed_signature(path, windows, 3)
    idx = [C.flat_index(w, d) for w in words]
    np.testing.assert_allclose(proj, full[..., idx], rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("tr", ["time_augment", "lead_lag", "basepoint",
                                "time_augment+lead_lag"])
def test_windowed_transform_matches_per_window_oracle(rng, tr):
    """transform= applies PER WINDOW, fused into the fold route's sweep:
    identical to signature(window_slice, transform=...) for every window
    (time restarts per window, basepoint is each window's first value)."""
    path = make_path(rng, 3, 20, 2)
    windows = np.asarray([[0, 20], [0, 5], [5, 12], [11, 20], [7, 8]],
                         np.int32)
    out = windowed_signature(jnp.asarray(path), windows, 3, transform=tr)
    ref = np.stack([np.asarray(C.signature(path[:, l:r + 1], 3,
                                           transform=tr))
                    for l, r in windows], axis=1)  # noqa: E741
    assert out.shape == ref.shape
    np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-5)


def test_windowed_transform_projection_subset(rng):
    d = 2
    path = jnp.asarray(make_path(rng, 2, 16, d))
    windows = np.asarray([[0, 8], [4, 16]], np.int32)
    from repro.core.transforms import transform_dim
    d_aug = transform_dim("lead_lag", d)
    words = [(0,), (2, 1), (1, 3, 0)]
    plan = make_plan(words, d_aug)
    proj = windowed_projection(path, windows, plan, transform="lead_lag")
    full = windowed_signature(path, windows, 3, transform="lead_lag")
    idx = [C.flat_index(w, d_aug) for w in words]
    np.testing.assert_allclose(proj, full[..., idx], rtol=1e-4, atol=1e-5)


def test_windowed_transform_pins_route_to_fold(rng):
    """Per-window transform semantics don't compose with Chen combination
    of one streamed pass (the streamed pass transforms the WHOLE path):
    an explicit route="chen" refuses, route="auto" silently takes fold."""
    path = jnp.asarray(make_path(rng, 1, 12, 2))
    windows = np.asarray([[0, 6], [3, 12]], np.int32)
    with pytest.raises(NotImplementedError, match="chen"):
        windowed_signature(path, windows, 3, transform="time_augment",
                           route="chen")
    out = windowed_signature(path, windows, 3, transform="time_augment",
                             route="auto")
    assert out.shape[1] == 2


def test_windowed_transform_ragged_clipping(rng):
    """With lengths, window [l, r] clips to [min(l, L_b), min(r, L_b)] per
    example BEFORE the transform applies — the time channel and basepoint
    see the clipped window, exactly like the per-example oracle."""
    path = make_path(rng, 3, 14, 2)
    lens = np.asarray([14, 9, 3], np.int32)
    windows = np.asarray([[0, 14], [2, 11], [5, 6]], np.int32)
    out = windowed_signature(jnp.asarray(path), windows, 3,
                             transform="time_augment+basepoint",
                             lengths=jnp.asarray(lens))
    for b, L in enumerate(lens):
        for k, (l, r) in enumerate(windows):  # noqa: E741
            lb, rb = min(l, L), min(r, L)
            ref = np.asarray(C.signature(
                path[b:b + 1, lb:rb + 1], 3,
                transform="time_augment+basepoint"))[0]
            np.testing.assert_allclose(np.asarray(out[b, k]), ref,
                                       rtol=1e-4, atol=1e-5,
                                       err_msg=f"b={b} window=({l},{r})")


def test_gradients_flow_through_windows(rng):
    path = jnp.asarray(make_path(rng, 2, 12, 2))
    windows = np.asarray([[0, 6], [3, 12]], np.int32)
    g = jax.grad(lambda p: jnp.sum(windowed_signature(p, windows, 3) ** 2))(
        path)
    assert g.shape == path.shape and bool(jnp.all(jnp.isfinite(g)))
    # increments outside every window get zero path-gradient contribution:
    # here steps 0..5 and 3..11 cover everything except nothing -> nonzero
    assert float(jnp.max(jnp.abs(g))) > 0


def test_window_helpers():
    ew = expanding_windows(10, stride=2)
    assert (ew[:, 0] == 0).all() and list(ew[:, 1]) == [2, 4, 6, 8, 10]
    sw = sliding_windows(10, 4, stride=3)
    assert [tuple(w) for w in sw] == [(0, 4), (3, 7), (6, 10)]
    dw = dyadic_windows(8, 3)
    assert (dw[:, 1] > dw[:, 0]).all()
    assert tuple(dw[0]) == (0, 8)          # level 0: the whole interval
    assert len(dw) == 1 + 2 + 4


def test_expanding_windows_keeps_path_tail():
    """stride ∤ M used to silently drop the tail: [0, M] must always close."""
    ew = expanding_windows(10, stride=3)
    assert list(ew[:, 1]) == [3, 6, 9, 10]
    assert tuple(ew[-1]) == (0, 10)
    # stride > M degenerates to the single full window
    assert [tuple(w) for w in expanding_windows(4, stride=7)] == [(0, 4)]
    with pytest.raises(ValueError):
        expanding_windows(0)


def test_sliding_windows_validates_length():
    with pytest.raises(ValueError, match="length"):
        sliding_windows(8, 9)              # length > M used to yield 0 windows
    with pytest.raises(ValueError, match="length"):
        sliding_windows(8, 0)
    with pytest.raises(ValueError, match="stride"):
        sliding_windows(8, 4, stride=0)
    assert [tuple(w) for w in sliding_windows(8, 8)] == [(0, 8)]


def test_empty_window_set_returns_empty_result(rng):
    """Used to crash with 'zero-size array to reduction operation maximum'."""
    path = jnp.asarray(make_path(rng, 2, 10, 3))
    out = windowed_signature(path, np.zeros((0, 2), np.int32), 3)
    assert out.shape == (2, 0, C.sig_dim(3, 3))
    plan = make_plan([(0,), (2, 1)], 3)
    proj = windowed_projection(path, np.zeros((0, 2), np.int32), plan)
    assert proj.shape == (2, 0, 2)


def test_out_of_range_windows_raise(rng):
    path = jnp.asarray(make_path(rng, 1, 10, 2))
    with pytest.raises(ValueError, match="window indices"):
        windowed_signature(path, np.asarray([[0, 11]], np.int32), 2)
    with pytest.raises(ValueError, match="l <= r"):
        windowed_signature(path, np.asarray([[5, 3]], np.int32), 2)


@given(st.integers(2, 3), st.integers(1, 3),
       st.lists(st.tuples(st.integers(0, 10), st.integers(1, 14)),
                min_size=1, max_size=5))
@settings(max_examples=12, deadline=None)
def test_random_windows_property(d, N, raw_windows):
    windows = np.asarray([(min(a, b - 1) if a < b else b - 1, b)
                          for a, b in raw_windows
                          if b >= 1], np.int32)
    windows[:, 0] = np.clip(windows[:, 0], 0, None)
    if len(windows) == 0:
        return
    rng = np.random.default_rng(d * 10 + N)
    path = make_path(rng, 2, 14, d)
    out = windowed_signature(jnp.asarray(path), windows, N)
    np.testing.assert_allclose(out, _oracle(path, windows, N),
                               rtol=2e-4, atol=1e-5)


def test_single_point_window_is_identity_signature(rng):
    """A window of length 1 covers a single increment; length 0 is empty."""
    path = jnp.asarray(make_path(rng, 1, 10, 2))
    windows = np.asarray([[4, 5]], np.int32)
    out = windowed_signature(path, windows, 2)
    seg = C.signature(path[:, 4:6], 2)
    np.testing.assert_allclose(out[:, 0], seg, rtol=1e-5, atol=1e-6)


def test_auto_route_within_15pct_of_best_on_fig3_grid():
    """Cost-model calibration check: on every measured point of the grid in
    ``tests/data/windows_route_grid_cpu.json``, the route
    ``select_route("auto")`` picks must be within 15% of the measured-best
    fixed route.  The timings are CPU wall times of the jax engine, the
    measurements the ``core/windows.py`` constants were fitted to; they rank
    nothing on the chip.  Catches constant drift: if someone retunes
    _CHEN_STEP_COST / _CHEN_ADVANTAGE into a regime the measured grid
    contradicts, this fails without timing anything."""
    import json
    import pathlib

    from repro.core.windows import select_route

    grid = (pathlib.Path(__file__).resolve().parent / "data"
            / "windows_route_grid_cpu.json")
    records = json.loads(grid.read_text())["records"]
    assert records, f"{grid.name} has no records"
    for rec in records:
        windows = sliding_windows(rec["M"], rec["wlen"], rec["stride"])
        assert windows.shape[0] == rec["K"], (
            f"window grid drifted: rebuilt K={windows.shape[0]} != "
            f"recorded K={rec['K']}")
        route = select_route("auto", windows, rec["M"])
        measured = {"fold": rec["fold_ms"], "chen": rec["chen_ms"]}
        best = min(measured.values())
        assert measured[route] <= 1.15 * best, (
            f"auto picked {route} ({measured[route]:.2f} ms) but best fixed "
            f"route costs {best:.2f} ms on {rec}")
