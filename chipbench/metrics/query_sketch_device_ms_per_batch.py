"""Device time of the JL query sketch kernel (``kernels/jl_sketch.py``)
per ``search_batch`` call: one ``[3Q, N]`` launch a micro-batch, at the
batch's padded non-zero width."""
PATTERN = r"^%jl_sketch_pallas"


def read(ctx):
    secs, calls = ctx.trace.per_call("bench.search_batch", PATTERN)
    if not calls or secs <= 0:
        return None
    return 1e3 * secs / calls
