"""99th percentile of the latency, due -> returned, of every request of
the window answered before the traced stretch began (the traced stretch is
the window's last seconds; the profiler's start and stop hold up the
requests after it).  Per layer, not end to end: host stalls of ~0.1 s set
it, and its runs spread too widely to hold it to a bound."""
import numpy as np


def read(ctx):
    win = ctx.window
    if win.latency_s is None or win.done_s is None:
        return None
    lat = win.latency_s[win.done_s < ctx.traced_from]
    lat = lat[np.isfinite(lat)]
    return float(np.percentile(lat, 99) * 1e3) if lat.size else None
