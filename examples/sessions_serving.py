"""Multi-tenant session serving demo: pooled streams end to end.

One `repro.serve.SessionStore` holds every tenant's running window
signature as a row of a single struct-of-arrays device pool.  This demo
walks the full serving lifecycle:

1. bursty multi-tenant ingest (`repro.data.session_tick_stream` traffic:
   heavy-tailed per-session rates + arrival/churn) delivered through
   continuous-batching `flush()` rounds — a bounded set of compiled shapes
   no matter what the traffic does;
2. scoring live sessions against cached references (gather a block of
   session signatures, one Gram call);
3. checkpoint -> "restart" (a fresh process would do the same) ->
   restore -> resume: the pool comes back bit-identical and the replayed
   traffic continues as if the restart never happened.

Run:  PYTHONPATH=src python examples/sessions_serving.py
"""
from __future__ import annotations

import tempfile

import jax.numpy as jnp
import numpy as np

from repro.checkpoint import Checkpointer
from repro.core import tensor_ops as tops
from repro.data import session_tick_stream
from repro.kernels import ops
from repro.serve import SessionStore
from repro.sigkernel import word_weights

D, DEPTH = 3, 3


def main() -> None:
    # 1) pooled ingest: sessions auto-admitted on first tick ---------------
    store = SessionStore(D, DEPTH, initial_sessions=16, ttl=50.0)
    traffic = session_tick_stream(40, D, seed=0, arrival_rate=1.5,
                                  churn_prob=0.02)
    for _ in range(6):
        r = next(traffic)
        store.ingest_many(r["sids"], r["counts"], r["ticks"],
                          auto_create=True)
        store.flush()
        for sid in r["departures"]:
            if sid in store:
                store.evict(sid)
    st = store.stats()
    print(f"pool: {st['sessions']} live sessions in {st['pool_size']} slots "
          f"(occupancy {st['occupancy']:.2f}), {st['updates']} ticks "
          f"applied in {st['flushes']} flushes")
    print(f"   compiled shapes: {st['compiled_shapes']} "
          f"(flush rungs {st['flush_shapes']}), "
          f"p99 staleness {st['p99_staleness_s']*1e3:.2f} ms, "
          f"evictions {st['evictions']}")

    # 2) score a block of live sessions against cached references ----------
    refs = np.cumsum(np.random.default_rng(7).standard_normal(
        (6, 33, D)).astype(np.float32) * 0.18, axis=1)
    ref_sigs = ops.signature(tops.path_increments(jnp.asarray(refs)), DEPTH,
                             backend="jax")
    w = jnp.asarray(word_weights(D, DEPTH))
    some = list(store._ids)[:5]
    K = ops.gram(store.block_features(some), ref_sigs, w, backend="jax")
    print(f"scored {len(some)} sessions x {refs.shape[0]} references: "
          f"nearest = {np.asarray(jnp.argmax(K, axis=-1)).tolist()}")

    # 3) checkpoint -> restart -> resume -----------------------------------
    ckpt_dir = tempfile.mkdtemp()
    ck = Checkpointer(ckpt_dir, async_save=False)
    store.checkpoint(ck, step=1)
    resume_state = traffic.state()           # data pipeline state rides along

    restored = SessionStore.restore(ck)      # ... in a fresh process
    replay = session_tick_stream(40, D, seed=0, arrival_rate=1.5,
                                 churn_prob=0.02)
    replay.restore(resume_state)
    same = all(np.array_equal(np.asarray(store.features(s)),
                              np.asarray(restored.features(s)))
               for s in store._ids)
    print(f"restored {len(restored)} sessions bit-identical: {same}")

    for src, st_ in ((traffic, store), (replay, restored)):
        r = next(src)
        live = [s for s in r["sids"] if s in st_]
        keep = [i for i, s in enumerate(r["sids"]) if s in st_]
        chunks = np.split(r["ticks"], np.cumsum(r["counts"])[:-1])
        if live:
            st_.ingest_many(live, r["counts"][keep],
                            np.concatenate([chunks[i] for i in keep]))
            st_.flush()
    same = all(np.array_equal(np.asarray(store.features(s)),
                              np.asarray(restored.features(s)))
               for s in store._ids)
    print(f"resumed both sides with the replayed round; still identical: "
          f"{same}")
    print("\nsessions serving OK — see examples/ragged_serving.py for the "
          "per-request (stateless) serving path")


if __name__ == "__main__":
    main()
