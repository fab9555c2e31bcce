"""bench/counts.py against a scalar Horner sweep that counts every
operation it makes, and whose results are checked against the plain
reference, forward and backward."""
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import counts, reference


class Tally:
    def __init__(self):
        self.ops = 0

    def __call__(self, n=1):
        self.ops += n


def horner_step(S, dx, C, depth, d, tally):
    """One Chen update S ⊗ exp(dx) over the closure C, word by word."""
    sc = {(k, i): dx[i] / k for k in range(1, depth + 1) for i in range(d)}
    tally(depth * d)
    t, acc = {}, {}
    for u in C:
        n = len(u)
        acc.setdefault((n, u[:1]), sc[(n, u[0])])
        for j in range(2, n + 1):
            v = u[:j - 1]
            if (n, v) not in t:
                t[(n, v)] = S[v] + acc[(n, v)]
                tally()
            if (n, u[:j]) not in acc:
                acc[(n, u[:j])] = t[(n, v)] * sc[(n - j + 1, u[j - 1])]
                tally()
    new = {}
    for u in C:
        new[u] = S[u] + acc[(len(u), u)]
        tally()
    return new, sc, t, acc


def horner_step_vjp(S_prev, dx, g_new, C, depth, d, tally):
    """Cotangents of one Horner step: (g over S_prev, g over dx)."""
    _, sc, t, acc = horner_step(S_prev, dx, C, depth, d, tally)
    tally.ops -= len(C)                      # the chain only, not S_new
    g_prev = dict(g_new)
    g_acc = {(len(u), u): g_new[u] for u in C}
    g_sc = {k: 0.0 for k in sc}
    for L in range(depth, 0, -1):
        g_t = {}
        for n, node in [k for k in acc if len(k[1]) == L]:
            g = g_acc.get((n, node), 0.0)
            if L == 1:
                g_sc[(n, node[0])] += g
                tally()
                continue
            v, i, k = node[:-1], node[-1], n - L + 1
            g_t[(n, v)] = g_t.get((n, v), 0.0) + g * sc[(k, i)]
            g_sc[(k, i)] += g * t[(n, v)]
            tally(4)
        for (n, v), g in g_t.items():        # t(n, v) = S[v] + acc(n, v)
            g_prev[v] += g
            tally()
            g_acc[(n, v)] = g
    g_dx = [sum(g_sc[(k, i)] / k for k in range(1, depth + 1))
            for i in range(d)]
    tally(2 * depth * d)
    return g_prev, g_dx


def sweep(incs, words, d):
    C = counts.closure(words)
    depth = max(len(w) for w in C)
    fwd, bwd = Tally(), Tally()
    S = {u: 0.0 for u in C}
    for dx in incs:
        S, *_ = horner_step(S, list(dx), C, depth, d, fwd)
    g = {u: 0.0 for u in C}
    for w in words:
        g[w] = 1.0 + 0.1 * len(w)            # a cotangent on the outputs
    g_incs = []
    for dx in incs[::-1]:                    # §4.2: reconstruct, then VJP
        S, *_ = horner_step(S, [-x for x in dx], C, depth, d, bwd)
        g, g_dx = horner_step_vjp(S, list(dx), g, C, depth, d, bwd)
        g_incs.append(g_dx)
    return S, fwd.ops, bwd.ops, np.asarray(g_incs[::-1])


def _check(words, d, M, ref_fn):
    rng = np.random.default_rng(0)
    incs = rng.normal(size=(M, d)) * 0.5
    S_T, fwd_ops, bwd_ops, g_incs = sweep(incs, words, d)
    depth = max(len(w) for w in words)
    nodes = counts.chain_nodes(words)
    per_step = counts.step_ops(nodes, d, depth)
    assert fwd_ops == M * per_step["fwd"]
    assert bwd_ops == M * per_step["bwd"]
    # the counted sweep is the signature, and its backward the gradient
    x = jnp.asarray(incs[None], jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref_fn(x))[0]
        cot = jnp.asarray([1.0 + 0.1 * len(w) for w in words])
        want_g = np.asarray(jax.grad(lambda z: jnp.sum(ref_fn(z) * cot))(x))[0]
    # the backward's reconstruction returns to the start state: zero
    np.testing.assert_allclose(
        [S_T[u] for u in counts.closure(words)], 0.0, atol=1e-9)
    S = {u: 0.0 for u in counts.closure(words)}
    for dx in incs:
        S, *_ = horner_step(S, list(dx), counts.closure(words), depth, d,
                            Tally())
    np.testing.assert_allclose([S[w] for w in words], want, rtol=1e-5,
                               atol=1e-6)
    np.testing.assert_allclose(g_incs, want_g, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("d,depth,M", [(2, 3, 3), (3, 2, 4), (2, 4, 2)])
def test_truncated_counts_match_scalar_sweep(d, depth, M):
    words = counts.all_words(d, depth)
    assert counts.truncated_nodes(d, depth) == counts.chain_nodes(words)
    _check(words, d, M, lambda x: reference.signature(x, depth))


def test_projected_counts_match_scalar_sweep():
    words = reference.sparse_leadlag_words(2, 3)          # 4 channels
    _check(words, 4, 3, lambda x: reference.projected(x, words, 4))


def test_cell_counts():
    """The t1_d4n5 step: 3,624 forward and 13,560 backward operations per
    step and path; the §8 word set has 260 words."""
    ops = counts.step_ops(counts.truncated_nodes(4, 5), 4, 5)
    assert ops == {"fwd": 3624, "bwd": 13560}
    c = counts.truncated_sweep(1024, 500, 4, 5)
    assert c["fwd_ops"] == 1024 * 500 * 3624
    assert c["fwd_bytes"] == 4 * (1024 * 500 * 4 + 1024 * 1364)
    assert len(reference.sparse_leadlag_words(5, 3)) == 260


def test_roofline_bound():
    share, bound = counts.roofline(197e12, 1.0, 2.0, 197e12, 819e9)
    assert (share, bound) == (50.0, "compute")
    share, bound = counts.roofline(1.0, 819e9, 4.0, 197e12, 819e9)
    assert (share, bound) == (25.0, "memory")


def test_closure_is_prefix_closed():
    words = [(0, 1, 2), (3,)]
    assert counts.closure(words) == [(0,), (3,), (0, 1), (0, 1, 2)]
    assert all(w[:n] in counts.closure(words) for w in words
               for n in range(1, len(w)))
    assert list(itertools.chain(counts.all_words(2, 2))) == [
        (0,), (1,), (0, 0), (0, 1), (1, 0), (1, 1)]
