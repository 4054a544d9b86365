"""Serving subsystem: continuous-batching decode over ONE paged KV
block pool (ISSUE 1 tentpole; the layer that multiplexes many concurrent
requests onto one compiled batched decode step), the radix prefix
cache and chunked-prefill admission that make admissions prefix-aware
and non-blocking (ISSUE 2 tentpole), the fault-tolerant runtime —
deadlines, cancellation, load shedding, deterministic fault injection,
and crash-safe snapshot/resume (ISSUE 3 tentpole) — and
self-speculative decoding: n-gram drafting with single-pass K-token
verification (ISSUE 4 tentpole) — and the streaming HTTP serving
gateway + client that turn the engine into a deployable server
(ISSUE 5 tentpole) — and paged KV memory, the engine's only KV
layout: one block-pool cache shared by decode slots and the prefix
trie (whose entries lease its blocks), with zero-copy prefix splices,
copy-on-write divergence, and a cold admission that prefills a dense
B=1 row and scatters it into blocks (ISSUE 6 tentpole) — and
the multi-replica router tier: a failure-tolerant prefix-affinity
front door over N gateway replicas with journaled in-flight replay
onto survivors (ISSUE 9 tentpole) — and fleet-wide distributed
tracing + federated metrics: router-minted ``X-DL4J-Trace`` contexts
stamped through to every engine span, a stitched skew-corrected
multi-lane ``/v1/trace``, and bucket-wise-merged
``/v1/fleet/metrics`` (ISSUE 10 tentpole) — and the elastic fleet
controller: SLO-driven autoscaling over subprocess/in-process replica
factories and zero-downtime rolling upgrades, every scale decision a
``fleet.scale`` span on the stitched trace (ISSUE 11 tentpole) — and
the tensor-parallel sharded decode engine: ``DecodeEngine(tp=N)``
turns the decode/verify/chunk executables into ``shard_map`` programs
over attention heads with per-shard head-sliced KV (bytes = total/TP)
behind the SAME layout-invariant host BlockTable, paired with a fused
pallas paged-attention decode kernel (ISSUE 12 tentpole) — and the
KV transfer plane: disaggregated prefill/decode roles with
cross-replica shipping of warmed KV blocks (framed binary
export/import, width-invariant across TP donors) and async
double-buffered decode rounds (ISSUE 14 tentpole,
``async_rounds=True`` / router ``kv_transfer=True``) — and the
durable router: a crash-safe write-ahead journal
(``serving/journal.py``, ``ServingRouter(journal_path=)``) that
makes the router itself as expendable as the replicas it fronts —
restart recovery replays open streams bit-identically, token-bucket
levels and warm beliefs survive the crash, and clients resume
dropped streams by SSE ``Last-Event-ID`` with zero duplicated and
zero lost tokens (ISSUE 15 tentpole)."""

from deeplearning4j_tpu.serving.block_pool import BlockPool, BlockTable
from deeplearning4j_tpu.serving.controller import FleetController
from deeplearning4j_tpu.serving.replica_proc import (
    LocalReplica,
    ReplicaProcess,
)

from deeplearning4j_tpu.serving.client import (
    GatewayClient,
    GatewayError,
    GatewayStream,
)
from deeplearning4j_tpu.serving.engine import DecodeEngine
from deeplearning4j_tpu.serving.faults import (
    FAULT_KINDS,
    FaultEvent,
    FaultPlan,
    ManualClock,
)
from deeplearning4j_tpu.serving.gateway import (
    ROLES,
    STATUS_OF_REASON,
    ServingGateway,
)
from deeplearning4j_tpu.serving.journal import (
    FSYNC_POLICIES,
    JournalError,
    WriteAheadJournal,
    read_records,
    recover_state,
)
from deeplearning4j_tpu.serving.kv_transfer import (
    KVTransferError,
    pack_prefix,
    unpack_prefix,
)
from deeplearning4j_tpu.serving.router import (
    REPLICA_STATES,
    RouterClient,
    ServingRouter,
)
from deeplearning4j_tpu.serving.prefix_cache import (
    PrefixHit,
    RadixPrefixCache,
)
from deeplearning4j_tpu.serving.sampler import (
    greedy_acceptance,
    residual_sample,
    sample_tokens,
    stochastic_acceptance,
)
from deeplearning4j_tpu.serving.scheduler import (
    FINISH_REASONS,
    GenerationResult,
    Request,
    Scheduler,
)
from deeplearning4j_tpu.serving.spec import NgramDraftTable
from deeplearning4j_tpu.serving.tenancy import (
    DEFAULT_TENANT,
    SYSTEM_TENANT,
    TenantRegistry,
    TenantSpec,
    TokenBucket,
    WeightedFairScheduler,
)
from deeplearning4j_tpu.serving.tp import TPContext

__all__ = [
    "BlockPool",
    "BlockTable",
    "DecodeEngine",
    "FAULT_KINDS",
    "FINISH_REASONS",
    "FaultEvent",
    "FaultPlan",
    "FSYNC_POLICIES",
    "FleetController",
    "GatewayClient",
    "GatewayError",
    "GatewayStream",
    "GenerationResult",
    "JournalError",
    "KVTransferError",
    "LocalReplica",
    "ManualClock",
    "NgramDraftTable",
    "ReplicaProcess",
    "PrefixHit",
    "REPLICA_STATES",
    "ROLES",
    "RadixPrefixCache",
    "Request",
    "RouterClient",
    "STATUS_OF_REASON",
    "Scheduler",
    "DEFAULT_TENANT",
    "SYSTEM_TENANT",
    "TenantRegistry",
    "TenantSpec",
    "TokenBucket",
    "WeightedFairScheduler",
    "TPContext",
    "ServingGateway",
    "ServingRouter",
    "WriteAheadJournal",
    "greedy_acceptance",
    "pack_prefix",
    "read_records",
    "recover_state",
    "residual_sample",
    "sample_tokens",
    "stochastic_acceptance",
    "unpack_prefix",
]
