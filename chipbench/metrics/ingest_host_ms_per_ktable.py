"""Host prep of ingest per thousand tables: the self time of the program's
``serve.ingest_sharded`` span less its ``merge.build_sharded`` and
``store.append`` children (``served_vectors``, the KMV sample, table
registration)."""
OUTER = "serve.ingest_sharded"
CHILDREN = ("merge.build_sharded", "store.append")


def _inside(inner, outer):
    return (inner["ts"] >= outer["ts"]
            and inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"]
            and inner["tid"] == outer["tid"])


def read(ctx):
    spans = ctx.spans
    outers = [s for s in spans if s["name"] == OUTER]
    builds = [s for s in spans if s["name"] == CHILDREN[0]]
    self_us, tables = 0.0, 0
    for o in outers:
        kids = [s for s in spans if s["name"] in CHILDREN and _inside(s, o)
                and not any(_inside(s, b) for b in builds if b is not s)]
        self_us += o["dur"] - sum(k["dur"] for k in kids)
        tables += int(o["args"].get("tables", 0))
    if not tables:
        return None
    return (self_us / 1e3) / (tables / 1e3)
