"""From a profiler trace (``.xplane.pb``) to the numbers the per-layer
metrics read.

The device's operations are the events of each device plane's "XLA Ops"
line (``/device:TPU:<n>``), named by their HLO instruction.  A control-flow
operation (a ``while``) spans the operations of its body, which are events
of their own, so each event counts its self time: its duration less that
of the events nested in it.  A trace recorded on the CPU has no device
plane; there the events that carry an ``hlo_op`` stat stand in for them,
which is what lets the reduction be tested without a chip.  The traced
window is the host annotation ``bench.window``.  Per device:

- busy: the union of operation intervals inside the window;
- per-scope time: the summed self times of operations whose ``op_name``
  metadata in the compiled HLO lies under a ``jax.named_scope`` of the
  benchmark's, with the backward as its ``transpose(jvp(<scope>))``;
- collectives: operations whose HLO opcode is a cross-device one.

Every time is averaged over the devices.  Idle gaps inside the window are
named by the innermost ``bench.*`` host annotation open at their midpoint.
"""
from __future__ import annotations

import collections
import glob
import os
import re

WINDOW = "bench.window"
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all", "collective-broadcast")
_INSTR = re.compile(r'^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=.*?op_name="([^"]*)"')
_NAME = re.compile(r"^%?([\w.\-]+)\s*=")


def hlo_op_names(texts) -> dict:
    """{instruction name: op_name metadata} from compiled HLO texts."""
    out = {}
    for text in texts or ():
        for line in text.splitlines():
            m = _INSTR.match(line)
            if m:
                out.setdefault(m.group(1), m.group(2))
    return out


def _stats(ev) -> dict:
    return dict(ev.stats)


def _load(path):
    from jax.profiler import ProfileData
    return ProfileData.from_file(path)


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _short(path: str, name: str) -> str:
    if not path:
        return re.sub(r"\.\d+$", "", name)
    parts = path.split("/")
    if parts and parts[0].startswith("jit("):
        parts = parts[1:]
    return "/".join(parts)[-120:] or name


def _device_ops(pd, op_names):
    """{device plane name: [(start, end, name, op path)]}."""
    devices = {}
    for plane in pd.planes:
        if not plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            evs = devices.setdefault(plane.name, [])
            for ev in line.events:
                m = _NAME.match(ev.name)
                name = m.group(1) if m else ev.name
                evs.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                            name, op_names.get(name, "")))
    if devices:
        return devices
    evs = []                                   # a trace recorded on the CPU
    for plane in pd.planes:
        for line in plane.lines:
            for ev in line.events:
                st = _stats(ev)
                if "hlo_op" in st:
                    name = str(st["hlo_op"])
                    evs.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                name, op_names.get(name, "")))
    return {"cpu": evs} if evs else {}


def _self_times(evs) -> list:
    """Each event's duration less that of the events nested directly in
    it; ``evs`` sorted by (start, -end)."""
    out = [e - s for s, e, _, _ in evs]
    stack = []
    for k, (s, e, _, _) in enumerate(evs):
        while stack and evs[stack[-1]][1] <= s:
            stack.pop()
        if stack:
            out[stack[-1]] -= e - s
        stack.append(k)
    return [max(t, 0.0) for t in out]


def _host_annotations(pd):
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("bench."):
                    out.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                ev.name))
    return out


def reduce(pd, scope: str = "bench_sig", hlo_texts=None, top: int = 10):
    """-> dict of seconds (averaged over devices) and the breakdown."""
    op_names = hlo_op_names(hlo_texts)
    notes = _host_annotations(pd)
    windows = [n for n in notes if n[2] == WINDOW]
    if not windows:
        raise ValueError(f"no {WINDOW!r} annotation in the trace")
    w0, w1, _ = max(windows, key=lambda n: n[1] - n[0])
    inner = [n for n in notes if n[2] != WINDOW]
    devices = _device_ops(pd, op_names)
    if not devices:
        raise ValueError("no device operations in the trace")
    bwd_scope = f"transpose(jvp({scope}"
    tot = collections.Counter()
    ops = collections.Counter()
    gaps = collections.Counter()
    for k, evs in enumerate(devices.values()):
        evs = sorted(((max(s, w0), min(e, w1), n, p) for s, e, n, p in evs
                      if e > w0 and s < w1), key=lambda v: (v[0], -v[1]))
        busy = _union([(s, e) for s, e, _, _ in evs])
        tot["busy"] += sum(e - s for s, e in busy)
        for (s, e, name, path), dt in zip(evs, _self_times(evs)):
            tot["ops"] += dt
            if scope in path:
                tot["scope"] += dt
                if bwd_scope in path:
                    tot["scope_bwd"] += dt
            if re.sub(r"\.\d+$", "", name).startswith(COLLECTIVES):
                tot["collective"] += dt
            ops[_short(path, name)] += dt
        if k == 0:
            edges = [w0] + [x for iv in busy for x in iv] + [w1]
            for a, b in zip(edges[::2], edges[1::2]):
                if b <= a:
                    continue
                mid = (a + b) / 2
                live = [n for n in inner if n[0] <= mid <= n[1]]
                who = min(live, key=lambda n: n[1] - n[0])[2] if live \
                    else "bench.window"
                gaps[who] += b - a
    n = len(devices)
    s = {k: v / n * 1e-9 for k, v in tot.items()}
    return {
        "n_devices": n,
        "window_s": (w1 - w0) * 1e-9,
        "busy_s": s.get("busy", 0.0),
        "op_s": s.get("ops", 0.0),
        "scope_s": s.get("scope", 0.0),
        "scope_bwd_s": s.get("scope_bwd", 0.0),
        "collective_s": s.get("collective", 0.0),
        "device_ops": [[name, v / n * 1e-9]
                       for name, v in ops.most_common(top)],
        "idle_gaps": [[name, v * 1e-9] for name, v in gaps.most_common(top)],
    }


def find_xplane(directory: str) -> str:
    found = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return max(found, key=os.path.getmtime)


def reduce_dir(directory: str, **kw) -> dict:
    return reduce(_load(find_xplane(directory)), **kw)
