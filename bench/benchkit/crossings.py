"""Arithmetic shared by the boundary round-trip and entropy readers in
``bench/metrics``: crossings are the harness's records of each call of
the round trip, spans the program's obs events."""


def of_kind(run, kind):
    return [c for c in run["crossings"] if c["kind"] == kind]


def roundtrip_ms(run, kind):
    """Mean host time of one crossing of ``kind`` (ms), or None."""
    cs = of_kind(run, kind)
    if not cs:
        return None
    return 1e3 * sum(c["t1"] - c["t0"] for c in cs) / len(cs)


ENTROPY_STAGES = ("entropy_encode", "entropy_decode")


def entropy_ms(run, kind):
    """Mean time per crossing of ``kind`` in the entropy coder's spans
    (client encode and cloud decode), from the obs spans whose start
    falls inside that crossing (ms), or None."""
    cs = of_kind(run, kind)
    if not cs or not run["spans"]:
        return None
    spans = sorted((e["t_start"], e["dur_s"]) for e in run["spans"]
                   if e["stage"] in ENTROPY_STAGES)
    total = 0.0
    for c in cs:
        total += sum(d for t, d in spans if c["wall0"] <= t < c["wall1"])
    return 1e3 * total / len(cs)
