"""Boundary round trip (``launch/serve.py`` loopback: client encode,
framing, socket, cloud decode, echo): mean host time of one prefill
crossing, from the harness's own timer around the round trip."""

from benchkit import crossings

UNIT = "ms"
SOURCE = "host_clock"


def read(run):
    return crossings.roundtrip_ms(run, "prefill")
