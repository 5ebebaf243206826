"""``repro.serving`` — the engines the harness measures.

Fixed-batch (``ServeEngine``) and slot-based continuous batching
(``ContinuousBatchingEngine``) with optional paged KV
(``kv_page_size``/``PagePool``), radix prefix caching
(``prefix_caching``/``PrefixCache``), speculative decoding
(``draft_model``/``spec_k``), SLO-aware chunked prefill
(``prefill_chunk_tokens``) and priority scheduling with preemption
(``scheduler=Scheduler(preemption=True)``).
``ShardedContinuousBatchingEngine`` runs the same loop tensor-parallel
under ``shard_map``; ``PrefillWorker`` + ``DisaggregatedEngine`` split
prefill and decode into separately metered fleets joined by paged
``KVHandoff``.  Every optional mode is token-identical to plain greedy
decode — CI gates on it (``scripts/paged_smoke.py``).  See
``docs/serving.md`` for the slot lifecycle and scheduling policy.
"""
from repro.serving.disagg import (  # noqa: F401
    DisaggregatedEngine, KVHandoff, PrefillWorker,
)
from repro.serving.engine import (  # noqa: F401
    ContinuousBatchingEngine, Request, ServeEngine,
    attribute_request_energy,
)
from repro.serving.scheduler import Scheduler  # noqa: F401
from repro.serving.kv_pages import (  # noqa: F401
    GARBAGE_PAGE, PagePool, PoolExhausted,
)
from repro.serving.prefix_cache import PrefixCache  # noqa: F401
from repro.serving.sharded import (  # noqa: F401
    ShardedContinuousBatchingEngine, tp_param_shardings,
)
from repro.serving.speculative import (  # noqa: F401
    damp_upper_layers, greedy_verify, speculative_sample, truncate_draft,
)
