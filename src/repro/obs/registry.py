"""Declared metric registry: every metric name the codebase may emit.

``SPECS`` is deliberately a **pure literal** tuple of dicts: the static
analysis pass (:mod:`repro.analysis.obs`, rule OB002) extracts it with
``ast.literal_eval`` -- no import, no jax -- renders the generated
``METRICS.md`` table from it, and pins the committed file against drift the
same way ``STREAMS.md`` pins the salt-stream registry.  Keep every entry a
plain dict of strings/tuples; no computed values, no comprehensions.

The runtime side (:mod:`repro.obs.metrics`) validates every
``counter()`` / ``gauge()`` / ``histogram()`` call against this table:
an undeclared metric name, a wrong kind, or a wrong label set raises at
the call site instead of silently forking the telemetry namespace.

Fields per spec:

    name    dotted metric name (``subsystem.metric``); counters end in
            ``_total`` by convention
    type    "counter" | "gauge" | "histogram"
    labels  tuple of label keys every series of this metric must carry
    unit    unit of the recorded value ("s", "B", "ppm", ...)
    help    one-line meaning, rendered into METRICS.md and the Prometheus
            HELP line
"""
from __future__ import annotations

SPECS = (
    # -- kernels / ops layer -------------------------------------------------
    {"name": "ops.launches_total", "type": "counter",
     "labels": ("op", "family", "packed"), "unit": "launches",
     "help": "Calls through a public repro.kernels.ops launch wrapper, by "
             "op, ambient serving family ('-' outside a family context) "
             "and whether the corpus value plane was a packed store's bf16 "
             "plane ('true'/'false'; '-' for ops without one)."},
    {"name": "ops.compiles_total", "type": "counter",
     "labels": ("op",), "unit": "compiles",
     "help": "Backend compiles (JAX's backend_compile_duration event), by "
             "the innermost open ops.* span of the compiling thread ('-' "
             "outside one): which launch recompiled."},
    {"name": "ops.autotune_resolved_total", "type": "counter",
     "labels": ("kernel", "source"), "unit": "resolutions",
     "help": "Autotune block-size resolutions at trace time: "
             "source='tuned' when the roofline cache supplied blocks, "
             "'default' when the kernel's declared defaults ran."},
    {"name": "ops.interpret_mode", "type": "gauge",
     "labels": (), "unit": "bool",
     "help": "1 when Pallas launches run under the interpreter (non-TPU "
             "backend), 0 for compiled TPU launches."},
    # -- data / store layer --------------------------------------------------
    {"name": "store.resident_bytes", "type": "gauge",
     "labels": ("family",), "unit": "B",
     "help": "Allocated device bytes (capacity x fields x bytes/row) of "
             "the most recently touched CorpusStore of each family."},
    {"name": "store.rows", "type": "gauge",
     "labels": ("family",), "unit": "rows",
     "help": "Live rows (per field) of the most recently touched "
             "CorpusStore of each family."},
    {"name": "store.appends_total", "type": "counter",
     "labels": ("family",), "unit": "appends",
     "help": "CorpusStore.append batches written, by family."},
    {"name": "store.grows_total", "type": "counter",
     "labels": ("family",), "unit": "growths",
     "help": "Capacity-doubling buffer growths, by family."},
    {"name": "merge.merges_total", "type": "counter",
     "labels": ("family",), "unit": "merges",
     "help": "merge_stores calls (pairwise shard-merge steps), by family."},
    # -- serving layer -------------------------------------------------------
    {"name": "serve.request_seconds", "type": "histogram",
     "labels": ("endpoint",), "unit": "s",
     "help": "Per-request latency by endpoint: 'search' times one query, "
             "'search_batch' times one micro-batch."},
    {"name": "serve.tenant_request_seconds", "type": "histogram",
     "labels": ("tenant",), "unit": "s",
     "help": "Per-request latency of tenant-scoped queries, by tenant."},
    {"name": "serve.queries_total", "type": "counter",
     "labels": (), "unit": "queries",
     "help": "Single-query search requests served."},
    {"name": "serve.batches_total", "type": "counter",
     "labels": (), "unit": "batches",
     "help": "Micro-batches served through search_batch."},
    {"name": "serve.batch_queries_total", "type": "counter",
     "labels": (), "unit": "queries",
     "help": "Individual queries served through search_batch."},
    {"name": "serve.tables_ingested_total", "type": "counter",
     "labels": (), "unit": "tables",
     "help": "Tables ingested into the serving index."},
    {"name": "serve.rows_ingested_total", "type": "counter",
     "labels": (), "unit": "rows",
     "help": "Raw table rows ingested into the serving index."},
    {"name": "query.sketch_lanes_total", "type": "counter",
     "labels": ("family", "kind"), "unit": "lanes",
     "help": "Lanes of the query sketch launches (rows x padded non-zero "
             "width), by family: kind='real' holds a non-zero, 'pad' is "
             "padding."},
    {"name": "query.fetch_bytes_total", "type": "counter",
     "labels": ("family",), "unit": "B",
     "help": "Bytes the device query path copies to the host (the "
             "candidates' scores, indices, join and sum estimates, Q x k "
             "x 16 B a call), by family."},
    # -- estimator quality ---------------------------------------------------
    {"name": "quality.ppm_error", "type": "gauge",
     "labels": ("family",), "unit": "ppm",
     "help": "Rolling (EWMA, alpha=0.2) normalized estimator error in "
             "parts-per-million, from sampled query pairs re-scored "
             "against the host oracle or ground truth, by family."},
    {"name": "quality.samples_total", "type": "counter",
     "labels": ("family",), "unit": "samples",
     "help": "Quality-channel re-score samples recorded, by family."},
)
