"""From a profiler trace to device busy time, op times and idle gaps.

``reduce_events`` does the arithmetic on plain tuples, so it can be
checked on hand-made intervals; ``load_xplane`` reads the
``.xplane.pb`` the JAX profiler writes into those tuples.

- Busy time is the union of the intervals in which an operation ran on
  a device (the ``XLA Ops`` line of each device plane), clipped to the
  window, averaged over the devices that ran anything.
- Per-op device time sums each op's clipped durations, the op named
  ``<program>/<HLO instruction>``.
- Each idle gap (window minus busy, per device) is labelled by the
  innermost host annotation covering its midpoint, from the names in
  ``labels``, else ``other``.
"""

from __future__ import annotations

import dataclasses

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclasses.dataclass
class Events:
    device: dict[str, list[tuple[str, int, int]]]   # plane -> (name, t0, t1)
    host: list[tuple[str, int, int]]                # annotations


def union_length(intervals) -> int:
    total, end = 0, None
    for a, b in sorted(intervals):
        if end is None or a > end:
            total += b - a
            end = b
        elif b > end:
            total += b - end
            end = b
    return total


def _merge(intervals):
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def _label(host, t):
    best = None
    for name, a, b in host:
        if a <= t < b:
            if best is None or b - a < best[2] - best[1]:
                best = (name, a, b)
    return best[0] if best else "other"


def reduce_events(ev: Events, window: tuple[int, int], labels=(),
                  top: int = 10) -> dict:
    """Busy/idle seconds, per-op seconds and idle time by host label."""
    w0, w1 = window
    host = [h for h in ev.host if h[0] in labels and h[2] > w0 and h[1] < w1]
    busy, ops, idle = [], {}, {}
    for plane, events in ev.device.items():
        clipped = [(n, max(a, w0), min(b, w1)) for n, a, b in events
                   if b > w0 and a < w1]
        if not clipped:
            continue
        for n, a, b in clipped:
            ops[n] = ops.get(n, 0) + (b - a)
        merged = _merge([(a, b) for _, a, b in clipped])
        busy.append(sum(b - a for a, b in merged))
        edges = [w0] + [t for ab in merged for t in ab] + [w1]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                lab = _label(host, (a + b) // 2)
                idle[lab] = idle.get(lab, 0) + (b - a)
    n_dev = max(len(busy), 1)
    s = 1e-9
    return {
        "busy_s": sum(busy) / n_dev * s,
        "window_s": (w1 - w0) * s,
        "devices": len(busy),
        "op_seconds": {k: v / n_dev * s for k, v in ops.items()},
        "device_ops": [[k, v / n_dev * s] for k, v in sorted(
            ops.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[k, v / n_dev * s] for k, v in sorted(
            idle.items(), key=lambda kv: -kv[1])[:top]],
    }


def op_key(module: str, event: str) -> str:
    """``<module>/<instruction>`` from an ``XLA Modules`` event name
    (``jit_f(1234)``) and an ``XLA Ops`` one (its HLO text)."""
    instr = event.split(" = ", 1)[0].lstrip("%")
    return f"{module.split('(', 1)[0]}/{instr}"


def load_xplane(path) -> Events:
    """Device op events (named by :func:`op_key`) and host annotations
    of one ``.xplane.pb``."""
    import bisect

    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    device, host = {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            lines = {line.name: list(line.events) for line in plane.lines}
            if OPS_LINE not in lines:
                continue
            mods = sorted((int(e.start_ns), e.name)
                          for e in lines.get(MODULES_LINE, []))
            starts = [t for t, _ in mods]
            ops = device.setdefault(plane.name, [])
            for e in lines[OPS_LINE]:
                t0 = int(e.start_ns)
                i = bisect.bisect_right(starts, t0) - 1
                mod = mods[i][1] if i >= 0 else "?"
                ops.append((op_key(mod, e.name), t0,
                            int(e.start_ns + e.duration_ns)))
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                host.extend((e.name, int(e.start_ns),
                             int(e.start_ns + e.duration_ns))
                            for e in line.events)
    return Events(device, host)


def window_of(ev: Events, name: str) -> tuple[int, int]:
    """The span of the host annotation ``name`` (the measured window)."""
    spans = [(a, b) for n, a, b in ev.host if n == name]
    if not spans:
        raise ValueError(f"no host annotation {name!r} in the trace")
    return min(a for a, _ in spans), max(b for _, b in spans)
