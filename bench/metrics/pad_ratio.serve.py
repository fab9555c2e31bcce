"""Padded over true increments fed to the engine, from
``DynamicBatcher.stats()`` over the traced run's window."""


def read(layer):
    c = getattr(layer, "counters", {})
    return c["padded_steps"] / c["true_steps"] if c.get("true_steps") else None
