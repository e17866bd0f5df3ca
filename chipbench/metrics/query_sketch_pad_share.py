"""Share of the query sketch launch's lanes that are padding:
``1 - sum(nnz) / sum(rows * width)`` over the program's ``query.dispatch``
spans of the traced stretch (obs ring), which carry the launch's rows
(3Q), padded non-zero width and real non-zeros.  A program whose dispatch
span carries no such attributes reads nothing."""


def read(ctx):
    spans = [e["args"] for e in ctx.spans if e["name"] == "query.dispatch"
             and {"rows", "width", "nnz"} <= set(e.get("args", {}))]
    lanes = sum(a["rows"] * a["width"] for a in spans)
    if not lanes:
        return None
    return 100.0 * (1.0 - sum(a["nnz"] for a in spans) / lanes)
