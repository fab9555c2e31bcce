"""Share of the step's device time spent under the transpose of the
benchmark's signature scope: the §4.2 backward."""


def read(layer):
    t = layer.trace
    if t["scope_bwd_s"] <= 0 or t["op_s"] <= 0:
        return None
    return 100.0 * t["scope_bwd_s"] / t["op_s"]
