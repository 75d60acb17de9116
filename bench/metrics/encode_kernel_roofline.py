"""Encode megakernel (``kernels/fused_clip_quant.py``): the least time
the chip needs for the boundary encode's bytes (the tensor read once in
the bf16 model dtype, the packed indices written, ``benchkit.work``) at
HBM peak, over the kernel's device time in the trace.  The kernel is
found by name; where the trace holds none of its events the metric is
left out."""

from benchkit import work

UNIT = "%"
SOURCE = "device_trace"
# the megakernel's custom call takes the name of the jitted encode
# around it (``kernels/ops.py``: ``_encode_fused_flat`` per tensor,
# ``_encode_fused_tiled`` per tile)
KERNEL = "/_encode_fused_"


def read(run):
    t = run["trace"]
    if not run["peaks"] or not t:
        return None
    secs = sum(s for name, s in t["op_seconds"].items() if KERNEL in name)
    if secs <= 0:
        return None
    need = sum(work.encode_bytes(c["elems"], run["index_bits"])
               for c in run["crossings"])
    return 100.0 * need / run["peaks"]["hbm_bytes_per_s"] / secs
