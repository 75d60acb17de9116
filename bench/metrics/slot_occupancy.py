"""Serve loop (``serving/engine.py``): share of slot-steps that held a
live request, from the engine's registry counters over the window."""

UNIT = "%"
SOURCE = "program_counter"


def read(run):
    c = run["counters"]
    if not c.get("slot_steps"):
        return None
    return 100.0 * c["active_slot_steps"] / c["slot_steps"]
