"""Boundary round trip: wire bits the edge client sent (frames and
headers included, as ``SubmitResult.bits_per_elem`` counts them) over
all boundary elements of every crossing in the window."""

UNIT = "bits/elem"
SOURCE = "program_counter"


def read(run):
    cs = run["crossings"]
    elems = sum(c["elems"] for c in cs)
    if not elems:
        return None
    return sum(c["bits_per_elem"] * c["elems"] for c in cs) / elems
