"""Device time in cross-device collectives over the traced window."""


def read(layer):
    t = layer.trace
    if t["collective_s"] <= 0:
        return None
    return 100.0 * t["collective_s"] / t["window_s"]
