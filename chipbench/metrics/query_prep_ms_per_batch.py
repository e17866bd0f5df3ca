"""Host query prep per ``search_batch`` micro-batch: the program's
``query.prep`` span (``served_vectors`` and the KMV sample of every query
of the micro-batch) per ``serve.search_batch`` call, from the obs ring of
the traced stretch."""
from chipbench import spans


def read(ctx):
    return spans.ms_per_batch(ctx.spans, "query.prep")
