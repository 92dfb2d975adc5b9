"""paddle_tpu.serving — continuous-batching inference engine.

Architecture (SERVING.md): Orca-style iteration-level scheduling +
vLLM-style paged KV management + SGLang-style radix prefix caching +
Sarathi-style chunked prefill, compiled into a bounded grid of bucketed
XLA programs over the chip-validated paged-attention kernels; a
resilience layer (ISSUE 3) adds request deadlines/abort, bounded-queue
admission control, supervised step retries with poison quarantine, and
snapshot/resume across device failures; speculative decoding (ISSUE 5,
`serving.spec`) drafts K candidate tokens per sequence (n-gram prompt
lookup or a smaller draft model) and verifies them against the paged
cache in one bucketed launch with KV rollback for rejected drafts; the
fleet front-end (ISSUE 7, `serving.fleet`) multiplexes a streaming API
over N in-process replicas with prefix-affinity routing, replica
supervision, and zero-loss failover via snapshot live-migration; the
cross-process tier (ISSUE 14) moves replicas into worker processes
over a framed TCPStore mailbox (`ProcessFleet`/`worker.py`/
`transport.py`) with crash-proof restart through heartbeat-shipped
snapshots and a persistent AOT compile cache
(`serving.compile_cache`), fronted by HTTP/SSE (`HttpFrontend`);
multi-LoRA serving (ISSUE 15, `serving.lora`) serves N adapters per
engine — paged adapter-weight storage under the BlockAllocator
discipline, a batched heterogeneous segment-bmm delta kernel, and the
adapter id threaded through radix keys, snapshots and fleet routing.
"""
from .engine import ServingEngine, tp_serving_mesh
from .program_cache import ProgramCache
from .compile_cache import CompileCache
from .errors import (EngineFailure, EngineOverloaded, PoisonedComputation,
                     SnapshotVersionError, TransientDeviceError)
from .kv_cache import BlockAllocator, BlocksExhausted, KVSequence, PAD_PAGE
from .metrics import ServingMetrics
from .radix_cache import RadixCache, RadixNode
from .scheduler import (PrefillChunk, Request, RequestState, ScheduleStep,
                        Scheduler)
from .lora import (AdapterBusy, AdapterError, AdapterLoadError,
                   AdapterNotLoaded, AdapterRegistry, LoRAAdapter)
from .spec import DraftModelProposer, NgramProposer, Proposer
from .supervisor import RetryPolicy, StepSupervisor, classify_failure
from .trace import FlightRecorder, RequestTrace, RequestTracer
from ..profiler.exposition import render_prometheus
from .fleet import (Channel, Fleet, FleetHandle, FleetServer, HttpFrontend,
                    PrefixAffinityRouter, ProcessFleet, RandomRouter,
                    Replica, ReplicaState, RoundRobinRouter, TokenStream,
                    TransportError, WorkerProc, WorkerState)

__all__ = ["ServingEngine", "BlockAllocator", "BlocksExhausted",
           "KVSequence", "PAD_PAGE", "ServingMetrics", "RadixCache",
           "RadixNode", "PrefillChunk", "Request", "RequestState",
           "ScheduleStep", "Scheduler", "EngineFailure", "EngineOverloaded",
           "PoisonedComputation", "TransientDeviceError",
           "SnapshotVersionError", "RetryPolicy",
           "StepSupervisor", "classify_failure", "Proposer",
           "NgramProposer", "DraftModelProposer", "Fleet", "FleetHandle",
           "FleetServer", "TokenStream", "Replica", "ReplicaState",
           "PrefixAffinityRouter", "RandomRouter", "RoundRobinRouter",
           "tp_serving_mesh", "ProgramCache", "RequestTracer",
           "RequestTrace", "FlightRecorder", "render_prometheus",
           "CompileCache", "Channel", "TransportError", "HttpFrontend",
           "ProcessFleet", "WorkerProc", "WorkerState",
           "AdapterRegistry", "LoRAAdapter", "AdapterError",
           "AdapterNotLoaded", "AdapterLoadError", "AdapterBusy"]
