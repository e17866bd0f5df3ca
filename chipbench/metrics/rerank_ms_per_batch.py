"""Host refinement per ``search_batch`` micro-batch: the program's
``query.rerank`` span (the KMV sample-correlation re-rank of each query's
device candidates, and the result lists) per ``serve.search_batch`` call,
from the obs ring of the traced stretch."""
from chipbench import spans


def read(ctx):
    return spans.ms_per_batch(ctx.spans, "query.rerank")
