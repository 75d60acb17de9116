"""Serve loop (``serving/engine.py``): host time per decode step outside
the round trip, the ``split_head`` (head dispatch, boundary fetch),
``split_tail`` (reconstruction transfer, tail dispatch) and
``token_read`` (sampled tokens to the host) spans of decode kind, over
the window's ``steps`` counter."""

from benchkit import stages

UNIT = "ms"
SOURCE = "program_span"


def read(run):
    return stages.step_host_ms(run, "decode")
