"""Scoring-as-a-service: an async batched TPU inference subsystem.

Every capability in this framework — sharded scoring, resident pools,
bucketed compiled shapes, the persistent compilation cache — was until
now only reachable through the offline AL driver.  This package opens
the ONLINE path: a labeling frontend (or any HTTP client) streams
images and gets back predictions AND acquisition scores
(margin/entropy/embedding) from the best checkpoint of a live or
finished AL experiment.

Architecture (the Podracer decoupling, arXiv:2104.06272: a continuously
running device executor fed by asynchronous request producers keeps the
accelerator saturated under irregular load):

  * ``batcher``  — an asyncio microbatching queue: requests coalesce up
    to ``max_batch`` rows or a ``max_latency_ms`` deadline, whichever
    comes first, and every dispatched batch is padded to a geometric
    bucket (pool.bucket_size) so the served shape set is small, fixed,
    and pre-compiled.  Bounded admission (429 upstream) and carry-over
    so a batch never exceeds ``max_batch``.
  * ``executor`` — ONE device-executor loop over the persistent mesh:
    loads ``best_rd_{n}`` via the existing checkpoint machinery, runs
    the SAME jitted scoring steps the offline path uses
    (strategies/scoring.make_prob_stats_step / make_embed_step — served
    outputs are bit-for-bit the offline scores at the same batch
    shape), double-buffers host->device transfer through
    data/cache.device_prefetch, and hot-reloads a newer round's best
    checkpoint between batches so a running experiment is served
    without downtime.
  * ``server``   — stdlib-asyncio HTTP front end: POST /v1/predict,
    POST /v1/score, GET /healthz, GET /metrics; explicit backpressure
    (429 + Retry-After when the queue is full) and graceful drain on
    SIGTERM (in-flight requests complete, then the process exits 0).
  * ``cli``      — the ``serve`` verb (``python -m active_learning_tpu
    serve --experiment_dir ...``), resolving model/dataset/view from
    the experiment's saved config echo and the checkpoint's own head
    shape.

No dependencies beyond the stdlib and the existing JAX stack.  Request
latency — not round wall-clock — is this subsystem's metric; see
``scripts/serve_loadgen.py``.
"""

from .batcher import MicroBatcher, QueueFullError, serve_buckets  # noqa: F401
from .executor import DeviceExecutor  # noqa: F401
from .server import ScoringServer  # noqa: F401
