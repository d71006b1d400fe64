"""Serving layer: request lifecycle, SLO-aware continuous-batching
scheduling, a streaming front-end over the ragged engine, and a
multi-replica fleet router on top.

This is the FastGen/MII serving surface the reference exposes
(``mii/batching/ragged_batching.py``, the DeepSpeed-FastGen blog's
throughput-under-SLA methodology) promoted into a first-class subsystem:
:class:`Request` descriptors with a validated state machine, pluggable
admission/preemption policies (FCFS baseline + SLO-aware
earliest-deadline-first), a :class:`ServingEngine` that owns the
background tick loop, backpressure, cancellation, graceful drain and
fault recovery — and a :class:`ServingFleet` that load-balances N engine
replicas behind the same call surface (least-loaded or
prefix-cache-affinity routing, failover via bit-exact resume,
disaggregated prefill/decode KV hand-off, telemetry-driven
autoscaling). The KV leak audit (:func:`block_balance_report` /
:func:`assert_block_balance`, re-exported from the cache, inference/kv_cache.py) is
part of the public serving contract: zero leaked pages after drain on
every replica. See docs/serving.md.
"""

from ..inference.kv_cache import (  # noqa: F401
    assert_block_balance,
    block_balance_report,
)
from .cell import (  # noqa: F401
    CellDigest,
    CellState,
    CellUnreachable,
    ServingCell,
    check_reachable,
)
from .fleet import Replica, ReplicaState, ServingFleet  # noqa: F401
from .kvtier import (  # noqa: F401
    ColdTier,
    CorruptExport,
    KVTier,
    PrefixDirectory,
    PrefixExport,
    prefix_hash,
)
from .region import Region  # noqa: F401
from .rollout import (  # noqa: F401
    RolloutController,
    RolloutPhase,
    TERMINAL_PHASES,
)
from .request import (  # noqa: F401
    InvalidTransition,
    Request,
    RequestState,
    TERMINAL_STATES,
)
from .router import (  # noqa: F401
    LeastLoadedRouter,
    NoHealthyReplica,
    PrefixAffinityRouter,
    ResidencyAwareRouter,
    RouterPolicy,
    make_router,
    prefix_key,
)
from .scheduler import (  # noqa: F401
    CapacityView,
    FCFSPolicy,
    SLOPolicy,
    SchedulerPolicy,
    make_policy,
)
from .server import ServingEngine, stream_tokens  # noqa: F401
