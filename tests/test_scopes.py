"""The program names its device phases: every backward the dispatch layer
picks carries ``pathsig.fwd`` / ``pathsig.bwd`` and the halves of its
reverse scan as ``jax.named_scope`` names in the compiled gradient's HLO
(``repro.obs.trace.DEVICE_SCOPES``), where a profiler trace reads them."""
import re

import jax
import jax.numpy as jnp
import pytest

from repro.core import all_words, projected_signature
from repro.kernels import ops
from repro.obs.trace import DEVICE_SCOPES
from tests.conftest import projected_engine

FWD, BWD, RECONSTRUCT, COTANGENT, PROJ_INDEX = DEVICE_SCOPES
OP_NAME = re.compile(r'op_name="([^"]*)"')
WORDS = ((0,), (1,), (0, 1), (1, 0), (0, 1, 2), (2, 2, 1))
LEADLAG_WORDS = ((0,), (3,), (0, 3), (3, 0), (1, 4, 2))
DENSE_LEADLAG_WORDS = tuple(all_words(6, 2)) + ((1, 4, 2),)


def _trunc(backward, stream=False, backend="jax"):
    def sig(x):
        out = ops.signature(x, 3, backend=backend, backward=backward,
                            stream=stream)
        return out[:, -1] if stream else out
    return sig


def _proj(backward, backend="jax", transform=None, words=WORDS,
          engine="rule"):
    def sig(x):
        with projected_engine(engine):
            return projected_signature(jnp.cumsum(x, axis=1), words,
                                       backward=backward, backend=backend,
                                       transform=transform)
    return sig


# name -> (signature of the increments, has a reconstruct half, projected)
CASES = {
    "inverse": (_trunc("inverse"), True, False),
    "stream_inverse": (_trunc("inverse", stream=True), True, False),
    "checkpoint": (_trunc("checkpoint"), False, False),
    "projected_inverse": (_proj("inverse"), True, True),
    "projected_checkpoint": (_proj("checkpoint"), False, True),
    # the reverse-scan kernel fuses the reconstruction into its pull-back
    "kernel_inverse": (_trunc("inverse", backend="pallas_interpret"), False,
                       False),
    "hybrid_inverse": (_proj("inverse", backend="hybrid"), True, False),
    "fused_leadlag": (_proj("inverse", backend="pallas_interpret",
                            transform="lead_lag", words=LEADLAG_WORDS,
                            engine="words"),
                      True, True),
    # the dense route: both truncated kernels, then a gather of the words
    "dense_leadlag": (_proj("inverse", backend="pallas_interpret",
                            transform="lead_lag", words=DENSE_LEADLAG_WORDS),
                      False, True),
}


def _op_names(sig) -> list:
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 6, 3)) * 0.3

    def loss(x):
        return jnp.sum(sig(x) ** 2)
    text = jax.jit(jax.grad(loss)).lower(x).compile().as_text()
    return OP_NAME.findall(text)


@pytest.mark.parametrize("case", sorted(CASES))
def test_compiled_gradient_names_its_phases(case):
    sig, reconstructs, projected = CASES[case]
    names = _op_names(sig)

    def under(scope):
        return [n for n in names
                if re.search(re.escape(scope) + r"(?![\w.])", n)]
    assert under(FWD) and under(BWD) and under(COTANGENT)
    assert all(BWD in n for n in under(COTANGENT))
    if reconstructs:
        assert under(RECONSTRUCT)
        assert all(BWD in n for n in under(RECONSTRUCT))
    else:
        assert not under(RECONSTRUCT)
    if projected:
        # the gathers' transposes are scatter-adds that keep the scope
        scatters = [n for n in under(PROJ_INDEX)
                    if n.endswith("scatter-add") and "transpose(" in n]
        assert scatters
        if reconstructs:
            assert any(f"transpose(jvp({PROJ_INDEX}))" in n
                       for n in scatters), scatters
    else:
        assert not under(PROJ_INDEX)
    # the backward is the forward's transpose, the forward never nests
    # inside itself
    assert all("transpose(" in n for n in under(BWD))
    assert not any(n.count(FWD + "/") > 1 for n in names)
