"""Share of the roofline of the projected signature's work over the
closure of the word set (forward and §4.2 backward) over the device time
under the benchmark's signature scope."""
from bench.counts import scope_roofline


def read(layer):
    return scope_roofline(layer, "proj_roofline.train")
