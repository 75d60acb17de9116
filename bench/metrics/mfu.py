"""Whole step: useful model FLOPs of the window over the window times
the chip's bf16 peak.  Useful FLOPs count unpadded prompt tokens and
fed-back served tokens through every layer, the head once per served
token, and attention over the causal context (``benchkit.work``)."""

from benchkit import work

UNIT = "%"
SOURCE = "host_clock"


def read(run):
    if not run["peaks"]:
        return None
    flops = sum(work.request_flops(run["conf"], len(r["prompt"]),
                                   len(r["stamps"]))
                for r in run["requests"])
    if not flops:
        return None
    return 100.0 * flops / (run["window_s"]
                            * run["peaks"]["bf16_flops_per_s"])
