"""Device time of scoring and top-k (``dataset_search._corr_scores``,
``ops.top_k``), from their jitted modules, per ``search_batch`` call."""
PATTERN = r"jit__corr_scores|jit_top_k"


def read(ctx):
    secs, calls = ctx.trace.per_call("bench.search_batch", PATTERN,
                                     modules=True)
    if not calls or secs <= 0:
        return None
    return 1e3 * secs / calls
