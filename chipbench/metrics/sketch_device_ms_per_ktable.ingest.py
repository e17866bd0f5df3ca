"""Device time of the ICWS sketch kernel (``kernels/icws_sketch.py``) per
thousand tables ingested: the kernel's time per ``bench.ingest_batch``
call over the tables of a batch."""
PATTERN = r"^%icws_sketch_pallas"


def read(ctx):
    secs, calls = ctx.trace.per_call("bench.ingest_batch", PATTERN)
    if not calls or secs <= 0:
        return None
    return 1e3 * (secs / calls) / (ctx.ingest_batch / 1e3)
