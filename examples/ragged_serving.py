"""Ragged serving demo: variable-length paths end to end.

Shows the three layers of `repro.ragged`:

1. exact variable-length signatures from one padded batch (`RaggedPaths` +
   `lengths=` through the engine dispatch — zero-masked padding is the
   identity, so the answers match per-example unpadded calls to the bit);
2. micro-batched serving with `repro.serve.DynamicBatcher`: mixed-length
   requests packed into a bounded ladder of compiled shapes;
3. kernel scoring of ragged traffic against cached references
   (`DynamicBatcher.scoring_service` over a `SigScoreEngine`).

Run:  PYTHONPATH=src python examples/ragged_serving.py
"""
from __future__ import annotations

import time

import jax.numpy as jnp
import numpy as np

from repro.core import signature
from repro.data import geometric_lengths
from repro.ragged import RaggedPaths
from repro.serve import DynamicBatcher, SigScoreEngine

D, DEPTH, MAX_LEN = 3, 4, 256


def make_requests(n: int, seed: int = 0) -> list[np.ndarray]:
    lengths = geometric_lengths(seed, n, MAX_LEN, min_steps=2)
    rng = np.random.default_rng(seed)
    out = []
    for L in lengths:
        steps = rng.standard_normal((int(L), D)).astype(np.float32)
        steps /= np.sqrt(max(int(L), 1))
        out.append(np.concatenate([np.zeros((1, D), np.float32),
                                   np.cumsum(steps, axis=0)], axis=0))
    return out


def main() -> None:
    reqs = make_requests(48)
    print(f"{len(reqs)} requests, lengths "
          f"{sorted(p.shape[0] - 1 for p in reqs)[:6]} ... "
          f"{max(p.shape[0] - 1 for p in reqs)}")

    # 1) one padded batch == per-example unpadded signatures, exactly
    rp = RaggedPaths.from_list(reqs)
    sig = signature(rp, DEPTH)                       # (B, D_sig)
    ref = signature(jnp.asarray(reqs[0])[None], DEPTH)[0]
    print(f"ragged batch: {tuple(sig.shape)}; max |err| vs unpadded call: "
          f"{float(np.max(np.abs(np.asarray(sig[0]) - np.asarray(ref)))):.1e}")

    # 2) dynamic batching: a bounded set of compiled shapes serves any mix
    db = DynamicBatcher.signature_service(D, DEPTH, max_len=MAX_LEN,
                                          backend="jax", min_bucket=32)
    t0 = time.perf_counter()
    tickets = [db.submit(p) for p in reqs]
    res = db.flush()
    dt = time.perf_counter() - t0
    st = db.stats()
    print(f"DynamicBatcher: {len(res)} requests in {dt*1e3:.0f} ms "
          f"(cold, incl. compiles) using {st['compiled_shapes']} compiled "
          f"shapes (ladder {st['ladder']}), padding overhead "
          f"{st['padding_overhead']:.2f}x")
    err = max(float(np.max(np.abs(np.asarray(res[t]) - np.asarray(sig[i]))))
              for i, t in enumerate(tickets))
    print(f"   max |err| vs the ragged batch: {err:.1e}")

    # 3) kernel scoring of ragged traffic against cached references
    refs = np.cumsum(np.random.default_rng(7).standard_normal(
        (8, 33, D)).astype(np.float32) * 0.18, axis=1)
    engine = SigScoreEngine(d=D, depth=DEPTH, batch=4,
                            references=jnp.asarray(refs), backend="jax")
    sb = DynamicBatcher.scoring_service(engine, max_len=MAX_LEN,
                                        mode="nearest", min_bucket=32)
    t2 = [sb.submit(p) for p in reqs[:8]]
    nearest = sb.flush()
    print(f"scoring_service(nearest): "
          f"{[int(nearest[t]) for t in t2]} (reference indices)")
    print("\nragged serving OK — see examples/sessions_serving.py for the "
          "STATEFUL serving path (pooled multi-tenant sessions with "
          "checkpoint/restore)")


if __name__ == "__main__":
    main()
