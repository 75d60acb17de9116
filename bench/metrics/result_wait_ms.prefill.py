"""Boundary round trip, after the send (``transport/client.py``): mean
time per prefill crossing in the ``result_wait`` span, from the last
frame written to the cloud's RESULT unpacked (cloud decode and echo)."""

from benchkit import stages

UNIT = "ms"
SOURCE = "program_span"


def read(run):
    return stages.stage_ms(run, "prefill", "result_wait")
