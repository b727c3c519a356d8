"""Continuous-batching serving: paged KV pool + request scheduler +
two static step programs, with prefix-sharing COW blocks, multi-tenant
fair-share admission, batched multi-LoRA decode and a scale-out fleet
tier (global admission/DRR/routing over N stock engines, disaggregated
prefill/decode, fleet-level prefix routing — see docs/serving.md)."""

from distributed_tensorflow_guide_tpu.serve.engine import (
    Event,
    ServeEngine,
    adapter_bank_shapes,
    build_step_fns,
    init_adapter_bank,
    paged_cache_pool,
    paged_config,
)
from distributed_tensorflow_guide_tpu.serve.fleet import (
    FleetScheduler,
)
from distributed_tensorflow_guide_tpu.serve.scheduler import (
    EngineOverloaded,
)
from distributed_tensorflow_guide_tpu.serve.paged_cache import (
    BlockPool,
    BlockStore,
    blocks_for,
    gather_view,
    table_row,
    write_chunk,
)
from distributed_tensorflow_guide_tpu.serve.prefix_index import (
    PrefixIndex,
)
from distributed_tensorflow_guide_tpu.serve.scheduler import (
    Request,
    Scheduler,
)

__all__ = [
    "BlockPool",
    "BlockStore",
    "EngineOverloaded",
    "Event",
    "FleetScheduler",
    "PrefixIndex",
    "Request",
    "Scheduler",
    "ServeEngine",
    "adapter_bank_shapes",
    "blocks_for",
    "build_step_fns",
    "gather_view",
    "init_adapter_bank",
    "paged_cache_pool",
    "paged_config",
    "table_row",
    "write_chunk",
]
