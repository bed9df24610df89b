"""paddle_tpu.serving — the inference serving tier.

Static-shape KV-cache autoregressive decode (compile once per length
bucket for prefill, exactly once for decode — O(1) per generated token)
plus a slot-based continuous-batching scheduler with a resilience layer
(deadlines, admission control / load shedding, OOM-safe degraded decode —
every request ends with exactly one terminal ``finish_reason`` from
``FINISH_REASONS``). See ``kv_cache.py`` for the cache/compiler contract,
``engine.py`` for the prefill/decode split, ``scheduler.py`` for request
scheduling and the failure story, ``benchmark/drivers/serve.py`` for the
throughput/latency benchmark and ``tools/chaos_serve.py`` for the
deterministic chaos harness.
"""
from .kv_cache import (  # noqa: F401
    KVCache,
    ChunkView,
    CountsView,
    DecodeView,
    PrefillView,
    StateDecodeView,
    StatePrefillView,
    default_buckets,
    pick_bucket,
)
from .draft import DraftProposer, NgramProposer  # noqa: F401
from .engine import (  # noqa: F401
    EncoderScorer,
    GenerationEngine,
    RecurrentStateError,
    RingCacheError,
)
from .scheduler import (  # noqa: F401
    FINISH_REASONS,
    CostAwareAdmission,
    Request,
    Scheduler,
    default_slo_monitor,
)

__all__ = [
    "KVCache",
    "ChunkView",
    "DecodeView",
    "PrefillView",
    "StateDecodeView",
    "StatePrefillView",
    "CountsView",
    "RecurrentStateError",
    "RingCacheError",
    "DraftProposer",
    "NgramProposer",
    "default_buckets",
    "pick_bucket",
    "GenerationEngine",
    "EncoderScorer",
    "Request",
    "Scheduler",
    "FINISH_REASONS",
    "CostAwareAdmission",
    "default_slo_monitor",
]
