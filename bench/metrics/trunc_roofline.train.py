"""Share of the roofline of the truncated signature's work (forward and
§4.2 backward) over the device time under the benchmark's signature scope.
Compute- or memory-bound per bench/counts.py; the bound goes to notes."""
from bench.counts import scope_roofline


def read(layer):
    return scope_roofline(layer, "trunc_roofline.train")
