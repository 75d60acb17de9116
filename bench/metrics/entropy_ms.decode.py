"""Entropy coder (``core/rans.py``, ``core/cabac.py`` via
``core/codec.py``): time in the ``entropy_encode`` and
``entropy_decode`` obs spans per decode crossing, both ends."""

from benchkit import crossings

UNIT = "ms"
SOURCE = "program_span"


def read(run):
    return crossings.entropy_ms(run, "decode")
