"""Operations and bytes that a signature sweep needs, from its shapes.

These count the algorithm, not an implementation: the levelwise Horner
update of the paper's Alg. 1 for the forward, and for the §4.2 backward
the reconstruction S_{j-1} = S_j ⊗ exp(-ΔX_j), the recomputed Horner chain
and its cotangent sweep.  So the count stays the same whichever kernel or
XLA fusion does the work, and a roofline share read against it is
comparable from one commit to the next.

One Horner step of a word set I (with prefix closure C) updates, for each
word u ∈ C of length n, S[u] += acc(n, u), where

    acc(n, u_1)       = ΔX^{u_1} / n                        (scaled once)
    t(n, v)           = S[v] + acc(n, v)                    1 add
    acc(n, v∘i)       = t(n, v) · ΔX^i / (n - |v|)          1 multiply

over the distinct chain nodes (n, v) of the closure.  Per step and path:

    forward   F = |T| + |A| + |C| + N·d   (t adds, acc multiplies, final
                                          adds, the N·d scaled letters
                                          ΔX^i / k)
    backward  F                          (reconstruct S_{j-1} with -ΔX)
              + N·d + |T| + |A|          (recompute the chain on S_{j-1})
              + 4·|A| + |T| + |A_1|      (cotangents: through each acc
                                          node to t and to the scaled
                                          letter, through each t node to
                                          S_{j-1}, and the first nodes)
              + 2·N·d                    (scaled letters back to ΔX)

where A_1 are the chain's first nodes acc(n, u_1).  ``bench/tests``
checks these against a scalar implementation that counts each operation.

Bytes are what must cross HBM once: the increments read, the output
written, and on the backward the increments, the terminal closure state
and the cotangent read and the increment cotangent written.
"""
from __future__ import annotations

import itertools

F32 = 4


def closure(words) -> list:
    out = {tuple(w[:n]) for w in words for n in range(1, len(w) + 1)}
    return sorted(out, key=lambda w: (len(w), w))


def chain_nodes(words) -> tuple[int, int, int, int]:
    """(|T|, |A|, |C|, |A_1|) of the Horner chains over the closure of
    ``words``."""
    C = closure(words)
    t_nodes, a_nodes = set(), set()
    first = {(len(u), u[:1]) for u in C}
    for u in C:
        n = len(u)
        for j in range(2, n + 1):
            t_nodes.add((n, u[:j - 1]))
            a_nodes.add((n, u[:j]))
    return len(t_nodes), len(a_nodes), len(C), len(first)


def truncated_nodes(d: int, depth: int) -> tuple[int, int, int, int]:
    """:func:`chain_nodes` of every word of length <= depth, in closed
    form."""
    T = sum(d ** (j - 1) for n in range(2, depth + 1) for j in range(2, n + 1))
    A = sum(d ** j for n in range(2, depth + 1) for j in range(2, n + 1))
    return T, A, sum(d ** n for n in range(1, depth + 1)), depth * d


def step_ops(nodes, d: int, depth: int) -> dict:
    """Operations per step and path: {"fwd", "bwd"}."""
    T, A, C, A1 = nodes
    fwd = T + A + C + depth * d
    return {"fwd": fwd, "bwd": 3 * T + 6 * A + C + A1 + 4 * depth * d}


def sweep(B: int, M: int, d: int, depth: int, nodes, n_out: int,
          in_elems: int | None = None) -> dict:
    """Ops and bytes of one forward and one §4.2 backward over a batch.

    ``M``, ``d`` are the steps and channels the sweep runs over (after any
    fused transform); ``in_elems`` is the size of the increments as they
    are read from HBM (default B·M·d)."""
    ops = step_ops(nodes, d, depth)
    x = F32 * (B * M * d if in_elems is None else in_elems)
    out = F32 * B * n_out
    state = F32 * B * nodes[2]
    return {"fwd_ops": B * M * ops["fwd"], "bwd_ops": B * M * ops["bwd"],
            "fwd_bytes": x + out, "bwd_bytes": 2 * x + state + out}


def truncated_sweep(B: int, M: int, d: int, depth: int) -> dict:
    nodes = truncated_nodes(d, depth)
    return sweep(B, M, d, depth, nodes, nodes[2])


def projected_sweep(B: int, M: int, d: int, words,
                    in_elems: int | None = None) -> dict:
    depth = max(len(w) for w in words)
    return sweep(B, M, d, depth, chain_nodes(words), len(words), in_elems)


def all_words(d: int, depth: int) -> list:
    return [w for n in range(1, depth + 1)
            for w in itertools.product(range(d), repeat=n)]


def roofline(ops: float, nbytes: float, seconds: float,
             peak_flops: float, peak_bw: float) -> tuple[float, str]:
    """(share of the roofline in %, the bound that applies)."""
    t_ops, t_bytes = ops / peak_flops, nbytes / peak_bw
    bound = "compute" if t_ops >= t_bytes else "memory"
    return 100.0 * max(t_ops, t_bytes) / seconds, bound


def scope_roofline(layer, name: str) -> float | None:
    """A per-layer reader's roofline share: the ops and bytes of the traced
    steps, per device, over the device time under the signature scope."""
    c, t = getattr(layer, "counts", None), layer.trace
    if c is None or t["scope_s"] <= 0:
        return None
    per_device = layer.steps / t["n_devices"]
    share, bound = roofline((c["fwd_ops"] + c["bwd_ops"]) * per_device,
                            (c["fwd_bytes"] + c["bwd_bytes"]) * per_device,
                            t["scope_s"], layer.peaks["flops_per_s"],
                            layer.peaks["hbm_bytes_per_s"])
    layer.notes[name] = f"{bound}-bound"
    return share
