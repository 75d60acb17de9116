"""Entropy coder (``core/cabac.py``'s ``auto`` choice): the share of the
decode crossings' boundary elements that the edge client coded with
rANS rather than serial CABAC.  Read from the ``n_elems`` and
``rans_elems`` of the ``entropy_encode`` spans that carry a ``coder``
and start inside a decode crossing; a program whose spans carry none
leaves the metric out."""

from benchkit import crossings

UNIT = "%"
SOURCE = "program_span"


def read(run):
    spans = [(e["t_start"], e["n_elems"], e["rans_elems"])
             for e in run["spans"]
             if e["stage"] == "entropy_encode" and "coder" in e]
    total = rans = 0
    for c in crossings.of_kind(run, "decode"):
        for t, n, r in spans:
            if c["wall0"] <= t < c["wall1"]:
                total += n
                rans += r
    return 100.0 * rans / total if total else None
