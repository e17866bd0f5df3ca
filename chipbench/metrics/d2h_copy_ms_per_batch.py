"""The copy of the join and post-join-sum planes (``[Q, P]`` f32 each) to
the host per ``search_batch`` micro-batch: the program's ``query.fetch``
span per ``serve.search_batch`` call, from the obs ring of the traced
stretch.  The span carries the bytes it moved."""
from chipbench import spans


def read(ctx):
    return spans.ms_per_batch(ctx.spans, "query.fetch")
