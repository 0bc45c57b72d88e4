from repro.serving.engine import (DrainBatchEngine, Request, ServingEngine,
                                  enable_compile_cache, load_snapshot,
                                  save_snapshot, validate_prompt)
from repro.serving.cascade_engine import (CascadeEngine, CascadeServingEngine,
                                          CircuitBreaker)
from repro.serving.faults import FaultError, FaultPlan, SeamSpec
from repro.serving.gateway import (BACKPRESSURE_POLICIES, EngineWedgedError,
                                   RequestHandle, ServingGateway,
                                   recover_engine)
from repro.serving.journal import RequestJournal
from repro.serving.kv_cache import (KVCacheBackend, PagedCache, PagedLayout,
                                    RING, RingCache, RingLayout, make_backend)
from repro.serving.sampler import (accepted_prefix_length, request_keys,
                                   sample_logits, sample_logits_batch,
                                   sample_logits_keyed)
from repro.serving.scheduler import (ChunkTask, PrefillProgress, Scheduler,
                                     StepPlan, bucket_for, chunk_buckets,
                                     prompt_buckets, request_rank,
                                     slots_for_hbm)
from repro.serving.sharding import (assert_cache_placement, cache_shardings,
                                    init_params, place_params, serving_rules)

__all__ = ["ServingEngine", "DrainBatchEngine", "Request", "CascadeEngine",
           "CascadeServingEngine", "CircuitBreaker",
           "FaultPlan", "FaultError", "SeamSpec",
           "ServingGateway", "RequestHandle", "BACKPRESSURE_POLICIES",
           "EngineWedgedError", "recover_engine", "RequestJournal",
           "save_snapshot", "load_snapshot",
           "sample_logits", "sample_logits_batch",
           "sample_logits_keyed", "request_keys", "accepted_prefix_length",
           "prompt_buckets", "bucket_for", "chunk_buckets",
           "validate_prompt", "Scheduler", "StepPlan", "ChunkTask",
           "PrefillProgress", "request_rank",
           "KVCacheBackend", "RingCache", "PagedCache", "RingLayout",
           "PagedLayout", "RING", "make_backend",
           "enable_compile_cache", "slots_for_hbm", "serving_rules",
           "init_params", "place_params", "cache_shardings",
           "assert_cache_placement"]
