"""Share of the JL corpus scan's roofline: the least time of one
micro-batch's scan (each field read once, and the six field pairs' dot
products at the bf16 peak, whichever binds) over the device time of the
linear fields kernel (``kernels/estimate.py``) per ``search_batch`` call."""
from chipbench import roofline

PATTERN = r"^%linear_estimate_fields_pallas"


def read(ctx):
    secs, calls = ctx.trace.per_call("bench.search_batch", PATTERN)
    if not calls or secs <= 0:
        return None
    d, svc = ctx.describe, ctx.service
    width, q = svc["width"], svc["micro_batch"]
    nbytes = roofline.scan_bytes(d["corpus_rows"], d["bytes_per_row"], q,
                                 4 * width)
    flops = roofline.scan_flops(d["corpus_rows"], q, width)
    least, bound = roofline.least_seconds(nbytes, flops, ctx.peaks)
    ctx.log(f"jl_scan_roofline: {nbytes:.0f} B and {flops:.0f} flop per "
            f"micro-batch; the {bound} bound binds")
    return 100.0 * least / (secs / calls)
