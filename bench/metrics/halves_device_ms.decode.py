"""Model halves: device time per decode step of the two split halves,
the programs ``jit_decode_to_boundary`` and ``jit_decode_from_boundary``
(``serving/engine.py``), from the trace's per-op seconds over the
window's ``steps`` counter.  A ``while`` loop's event spans the ops of
its body, which are counted on their own, so the loop is left out.  A
program whose halves carry other names reads nothing."""

from benchkit import stages

UNIT = "ms"
SOURCE = "device_trace"
PROGRAMS = ("jit_decode_to_boundary", "jit_decode_from_boundary")


def read(run):
    return stages.programs_ms(run, PROGRAMS)
