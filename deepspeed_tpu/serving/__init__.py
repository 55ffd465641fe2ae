"""Production inference serving: continuous batching over a paged KV
cache (see serving/server.py for the subsystem map)."""

from deepspeed_tpu.serving.kv_cache import (BlockAllocator,  # noqa: F401
                                            BlockAllocatorError,
                                            PagedKVCache)
from deepspeed_tpu.serving.paged_attention import (  # noqa: F401
    paged_chunk_attention, paged_decode_attention)
from deepspeed_tpu.serving.prefill import ChunkedPrefill  # noqa: F401
from deepspeed_tpu.serving.router import (RouteDecision,  # noqa: F401
                                          ServingRouter)
from deepspeed_tpu.serving.runner import (PagedGPT2Runner,  # noqa: F401
                                          PagedRunner)
from deepspeed_tpu.serving.sampling import (sample_tokens,  # noqa: F401
                                            top_p_filter)
from deepspeed_tpu.serving.scheduler import (  # noqa: F401
    ContinuousBatchingScheduler, Request, RequestState, StepPlan)
from deepspeed_tpu.serving.server import (RequestOutput,  # noqa: F401
                                          ServingEngine,
                                          ServingLivelockError)
