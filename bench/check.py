"""The numbers that decide ``correct``: each a gap between what the timed
path produced and the plain reference, held to a limit of its own
(``bench/limits/<cell>.json``; the readings each limit was set from are in
PERF.md)."""
from __future__ import annotations

import numpy as np
import jax


def _norm(x) -> float:
    return float(np.sqrt(np.sum(np.square(np.asarray(x, np.float64)))))


def leaf_norms(tree) -> list[float]:
    return [_norm(x) for x in jax.tree.leaves(tree)]


def loss_gap(losses, ref_losses) -> float:
    """Worst relative gap of the per-step losses."""
    return max(abs(a - b) / max(abs(b), 1e-30)
               for a, b in zip(losses, ref_losses))


def norm_gap(tree, ref_tree, keep=None) -> float:
    """Worst leaf's gap between the norm of ``tree``'s leaf and the
    reference's, over the reference's norm of that leaf or of the median
    leaf, whichever is larger.  ``keep`` masks the leaves compared."""
    got, want = leaf_norms(tree), leaf_norms(ref_tree)
    med = float(np.median(want))
    keep = keep or [True] * len(want)
    gaps = [abs(g - w) / max(w, med, 1e-30)
            for g, w, k in zip(got, want, keep) if k]
    return max(gaps)


def moved_leaves(ref_grad, rel: float = 1e-3) -> list[bool]:
    """Leaves whose reference gradient is at least ``rel`` of the median
    leaf's: the others move under Adam by round-off alone."""
    norms = leaf_norms(ref_grad)
    med = float(np.median(norms))
    return [n >= rel * med for n in norms]


def train_numbers(prog: dict, ref: dict, loss_steps: int = 3) -> dict:
    """prog/ref: {"losses": [3 floats], "grad": first gradient tree,
    "delta": parameter change after three steps}; the losses of the first
    ``loss_steps`` steps are compared."""
    return {
        "loss_gap": loss_gap(prog["losses"][:loss_steps],
                             ref["losses"][:loss_steps]),
        "grad_gap": norm_gap(prog["grad"], ref["grad"]),
        "update_gap": norm_gap(prog["delta"], ref["delta"],
                               moved_leaves(ref["grad"])),
    }


def row_error(got: np.ndarray, want: np.ndarray) -> float:
    """Worst row's largest absolute error over that row's largest
    magnitude."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    scale = np.maximum(np.abs(want).max(axis=-1), 1e-30)
    return float((np.abs(got - want).max(axis=-1) / scale).max())


def verdict(numbers: dict, limits: dict) -> bool:
    return all(np.isfinite(v) and v <= limits[k] for k, v in numbers.items())
