"""Names of the program's trace annotations, each defined once.

Device stages are ``jax.named_scope``s. A scope is metadata only: it lands
in the ``op_name`` of every HLO operation it covers, so the compiled
program is the same with or without a profiler. The scopes never nest, so
an operation carries at most one of them; ``launch.hlo_analysis.op_scopes``
maps a compiled program's instructions to them.

Host spans are ``jax.profiler.TraceAnnotation``s in the serving loop
(``serve/slots.py``) and its feeder thread. They write into the
profiler's trace, on the clock of the device planes, and cost about a
microsecond each when no profiler runs.
"""
from __future__ import annotations

# device stages (jax.named_scope)
CONVERT_ORDERING = "convert.ordering"   # key build and the full sorts
CONVERT_POINTER = "convert.pointer"     # CSC pointer build
SAMPLE_SELECT = "sample.select"         # k-hop neighbour selection
SAMPLE_REINDEX = "sample.reindex"       # VID dedup and rename
SAMPLE_RECONVERT = "sample.reconvert"   # the subgraph's ordering + pointers
SERVE_GATHER = "serve.gather"           # feature gather
SERVE_FORWARD = "serve.forward"         # GNN forward and argmax
DELTA_APPLY = "delta.apply"             # one streamed edge update
INFER_PROJECT = "infer.project"         # whole-graph GAT: projections, skips
INFER_EDGE = "infer.edge"               # whole-graph GAT: edge softmax, sums

DEVICE_SCOPES = (CONVERT_ORDERING, CONVERT_POINTER, SAMPLE_SELECT,
                 SAMPLE_REINDEX, SAMPLE_RECONVERT, SERVE_GATHER,
                 SERVE_FORWARD, DELTA_APPLY, INFER_PROJECT, INFER_EDGE)

# host spans (jax.profiler.TraceAnnotation)
ADMIT = "serve.admit"                   # an admission poll with slots free
ADMIT_WINDOW = "serve.admit_window"     # the bounded admission window
IDLE = "serve.idle"                     # the poll while no slot is active
STEP = "serve.step"                     # dispatch of one slot step
WAIT = "serve.wait"                     # waiting for a step's emission
ROUTE = "serve.route"                   # emission to host, routing
UPDATE = "serve.update"                 # a held update, until its CSC is ready
FEED = "serve.feed"                     # the feeder padding one row

HOST_SPANS = (ADMIT, ADMIT_WINDOW, IDLE, STEP, WAIT, ROUTE, UPDATE, FEED)
