"""Names of the served path's profiler spans and device scopes.

Host spans are ``jax.profiler.TraceAnnotation``s: they cost well under a
microsecond each while no profiler runs, and while one runs they land in
its trace on the same clock as the device's operation events.  Device
scopes are ``jax.named_scope``s: they exist only while a program is
traced, end up in its ops' ``op_name`` metadata and cost nothing at run
time.  Every name starts with ``qwyc.``; where scopes nest, an op belongs
to the innermost one.

Host spans, outermost first:

* ``FLUSH``: one non-empty ``QWYCServer.flush`` (metadata: ``index``, the
  flush's number on its server, and ``n``, its rows).  Its phases:
  ``FLUSH_STACK`` (stacking the queue), ``FLUSH_PREPARE`` (executor state,
  padding to capacity on the host, the operand's upload),
  ``FLUSH_SORT_KEY`` (launching the sorted-kernel policy's key program,
  which sorts on the device), ``RUN_DISPATCH`` / ``RUN_FETCH`` /
  ``RUN_STATS`` (inside an on-device executor's ``run``: building the row
  buffers and launching the program; the blocking read of its results,
  which includes waiting for the device; the per-stage counts) and
  ``FLUSH_FINISH`` (audit, per-row results, server statistics).
* ``DRAIN``: ``QWYCServer.drain``'s merge of results.
* ``COMPILE``: a device program lowered and compiled on a live server.

Device scopes: ``SCORE_DECIDE`` (a stage's scoring and decide kernels),
``COMPACT`` (the rest of a stage: row gathers, exit scatters, survivor
repacking), ``FINALIZE`` (after the stage loop), ``SORT_KEY`` (the sort-key
program) and ``COLLECTIVE`` (the sharded program's all-gathers and psums).
Inside a neural stage's scoring: ``MLA``, ``FFN``, ``MOE_ROUTE``,
``MOE_EXPERTS`` and ``MOE_SHARED`` (``models/transformer.py``,
``models/moe.py``).
"""

FLUSH = "qwyc.flush"
FLUSH_STACK = "qwyc.flush.stack"
FLUSH_PREPARE = "qwyc.flush.prepare"
FLUSH_SORT_KEY = "qwyc.flush.sort_key"
FLUSH_FINISH = "qwyc.flush.finish"
RUN_DISPATCH = "qwyc.run.dispatch"
RUN_FETCH = "qwyc.run.fetch"
RUN_STATS = "qwyc.run.stats"
DRAIN = "qwyc.drain"
COMPILE = "qwyc.compile"

SCORE_DECIDE = "qwyc.score_decide"
COMPACT = "qwyc.compact"
FINALIZE = "qwyc.finalize"
SORT_KEY = "qwyc.sort_key"
COLLECTIVE = "qwyc.collective"
# inside a neural stage: a layer's latent attention, its dense FFN, and
# the MoE layer's routing and dispatch, held-expert kernel and shared
# experts (``_`` and not ``.`` after ``moe``, so that a reader taking the
# innermost ``qwyc.<name>`` keeps the three apart)
MLA = "qwyc.mla"
FFN = "qwyc.ffn"
MOE_ROUTE = "qwyc.moe_route"
MOE_EXPERTS = "qwyc.moe_experts"
MOE_SHARED = "qwyc.moe_shared"
