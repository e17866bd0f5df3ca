"""Share of the traced window in which no op ran on the device: 1 - union
of op intervals / window.  One reader for every cell
(``device_idle_share.query``, ``device_idle_share.ingest``); in query cells
the breakdown splits it into time with no request due and host time inside
``search_batch``."""


def read(ctx):
    return 100.0 * (1.0 - ctx.trace.busy_s() / ctx.trace.window_s())
