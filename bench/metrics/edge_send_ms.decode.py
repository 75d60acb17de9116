"""Boundary round trip, edge side (``transport/client.py``): mean time
per decode crossing in the ``edge_send`` span, from the submit to the
last frame written (chunk encode and socket writes)."""

from benchkit import stages

UNIT = "ms"
SOURCE = "program_span"


def read(run):
    return stages.stage_ms(run, "decode", "edge_send")
