"""Arithmetic shared by the readers of the program's stage spans (the
engine's ``split_head`` / ``split_roundtrip`` / ``split_tail`` /
``token_read``, the edge client's ``edge_send`` / ``result_wait``) and of
the split halves' device time.  Spans are attributed to a crossing by
their start, inside the harness's ``wall0``/``wall1`` of that crossing."""

from . import crossings

# the serve loop's host work per step, round trip excluded
STEP_STAGES = ("split_head", "split_tail", "token_read")


def stage_ms(run, kind, stage):
    """Mean time per crossing of ``kind`` in the spans of ``stage`` that
    start inside that crossing (ms), or None without such spans."""
    cs = crossings.of_kind(run, kind)
    spans = [(e["t_start"], e["dur_s"]) for e in run["spans"]
             if e["stage"] == stage]
    if not cs or not spans:
        return None
    total = sum(d for c in cs for t, d in spans
                if c["wall0"] <= t < c["wall1"])
    return 1e3 * total / len(cs)


def step_host_ms(run, kind):
    """Host time per engine step in the step stages of ``kind`` (ms),
    over the window's ``steps`` counter, or None without such spans."""
    steps = run["counters"].get("steps")
    durs = [e["dur_s"] for e in run["spans"]
            if e["stage"] in STEP_STAGES and e.get("kind") == kind]
    if not steps or not durs:
        return None
    return 1e3 * sum(durs) / steps


# control-flow ops: the trace's event for one spans its body's ops, which
# the trace also lists, so counting both would count the body twice
CONTAINERS = ("while", "conditional", "call")


def programs_ms(run, programs):
    """Device time of the ops of ``programs`` (op keys
    ``<program>/<instruction>``, control-flow containers left out) per
    engine step (ms), or None where the trace holds none of their ops."""
    t = run["trace"]
    steps = run["counters"].get("steps")
    if not t or not steps:
        return None
    prefixes = tuple(p + "/" for p in programs)
    secs = sum(s for name, s in t["op_seconds"].items()
               if name.startswith(prefixes)
               and name.split("/", 1)[1].split(".")[0] not in CONTAINERS)
    if secs <= 0:
        return None
    return 1e3 * secs / steps
