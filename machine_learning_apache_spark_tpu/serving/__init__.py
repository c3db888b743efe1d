"""Request-level serving layer over the compiled decode core.

The repo's inference story stops at ``inference.Translator`` — a one-shot,
caller-owns-the-batch API. This package adds the layer the ROADMAP's
"millions of users" north star needs: concurrent callers share a bounded
admission queue (``queue``), and a background engine (``engine``) drives
the paged decode loop while ``metrics`` keeps the latency/throughput
ledger (padding-waste accounting included). Entry point:
``Translator.serve()``.

One engine drives two runtimes through one interface: the
encoder-decoder ``paged_runtime`` and the decoder-only ``lm_runtime``
(imported where ``LanguageModel.serve()`` takes it), whose prefix reuse is
``kv_pages.SnapshotCache`` (pages and a linear-attention state together).

A refcounted page pool + prefix cache (``kv_pages``) backs one device
page store; a token-budget admission picker
(``batcher.TokenBudgetBatcher``) paces chunked prefill; one compiled
ragged decode program serves any occupancy/length mix
(``paged_runtime``); a fixed row pool bounds the requests decoding at
once (``kv_slots``).
"""

from machine_learning_apache_spark_tpu.serving.batcher import (
    TokenBudgetBatcher,
)
from machine_learning_apache_spark_tpu.serving.engine import (
    EngineStopped,
    InternalError,
    ServingEngine,
)
from machine_learning_apache_spark_tpu.serving.kv_pages import (
    NULL_PAGE,
    KVPagePool,
    PrefixCache,
    SnapshotCache,
    prefix_digest,
)
from machine_learning_apache_spark_tpu.serving.kv_slots import KVSlotPool
from machine_learning_apache_spark_tpu.serving.metrics import (
    Histogram,
    ServingMetrics,
)
from machine_learning_apache_spark_tpu.serving.paged_runtime import (
    PagedDecodeRuntime,
)
from machine_learning_apache_spark_tpu.serving.queue import (
    Backpressure,
    DeadlineExceeded,
    RequestQueue,
    ServeRequest,
)

__all__ = [
    "Backpressure",
    "DeadlineExceeded",
    "EngineStopped",
    "Histogram",
    "InternalError",
    "KVPagePool",
    "KVSlotPool",
    "NULL_PAGE",
    "PagedDecodeRuntime",
    "PrefixCache",
    "RequestQueue",
    "ServeRequest",
    "ServingEngine",
    "ServingMetrics",
    "SnapshotCache",
    "TokenBudgetBatcher",
    "prefix_digest",
]
