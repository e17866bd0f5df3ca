"""Share of the ICWS corpus scan's roofline: the least time of one
micro-batch's scan (each field read once, :mod:`chipbench.roofline`) over
the device time of the fused fields kernel (``kernels/estimate.py``) per
``search_batch`` call (one micro-batch each)."""
from chipbench import roofline

PATTERN = r"^%estimate_fields_pallas"


def read(ctx):
    secs, calls = ctx.trace.per_call("bench.search_batch", PATTERN)
    if not calls or secs <= 0:
        return None
    d, svc = ctx.describe, ctx.service
    nbytes = roofline.scan_bytes(d["corpus_rows"], d["bytes_per_row"],
                                 svc["micro_batch"], 8 * svc["m"])
    least, _ = roofline.least_seconds(nbytes, 0.0, ctx.peaks)
    return 100.0 * least / (secs / calls)
