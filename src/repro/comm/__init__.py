"""repro.comm — the unified communication API (paper Algorithm 1).

One typed entry point for multi-path P2P, collectives, tuning, and plan
caching. Layering (DESIGN.md §1):

* :mod:`repro.comm.config`      — :class:`CommConfig` (+ ``from_env``)
* :mod:`repro.comm.plan`        — transfer-plan data model
* :mod:`repro.comm.graph`       — :class:`TransferGraph` heterogeneous DAG IR
* :mod:`repro.comm.passes`      — chunk-interleaving scheduler passes (§2.2)
* :mod:`repro.comm.capture`     — whole-iteration step capture (§2.4)
* :mod:`repro.comm.policy`      — pluggable :class:`PathPolicy` strategies
* :mod:`repro.comm.planner`     — route enumeration + plan construction
* :mod:`repro.comm.cache`       — compiled-plan LRU (CUDA-Graph analogue)
* :mod:`repro.comm.telemetry`   — per-dispatch stage-timing recorder (§4.4c)
* :mod:`repro.comm.calibration` — measured-feedback model fitting (§4.4c)
* :mod:`repro.comm.collectives` — bidirectional-ring collectives
* :mod:`repro.comm.health`      — link-fault injection + health monitor (§4.6)
* :mod:`repro.comm.engine`      — executable transfer engine (shard_map)
* :mod:`repro.comm.session`     — :class:`CommSession` facade

Typical use::

    from repro.comm import CommConfig, CommSession

    session = CommSession(CommConfig(max_paths=3))
    out = session.send(message, src=0, dst=1)
    print(session.stats()["cache"])

The legacy ``repro.core.paths`` / ``repro.core.multipath`` /
``repro.core.plan_cache`` / ``repro.core.collectives`` modules are
deprecated shims over this package.
"""

from repro.comm.config import (  # noqa: F401
    COLLECTIVE_STRATEGIES, POLICY_NAMES, SCHEDULE_NAMES, VALIDATE_MODES,
    CommConfig)
from repro.comm.plan import (  # noqa: F401
    PathAssignment, TransferGroup, TransferPlan, TransferRequest)
from repro.comm.graph import (  # noqa: F401
    BUFFER_EDGE, ComputeNode, CopyNode, DepEdge, TransferGraph,
    canonical_digest, lower)
from repro.comm.capture import (  # noqa: F401
    BufferRef, BufferSpec, CapturedStep, StepCapture, captured_psum,
    emit_step, lower_step)
from repro.comm.passes import (  # noqa: F401
    AutoSchedule, CriticalPathSchedule, DepthFirstSchedule, GraphPass,
    RoundRobinSchedule, apply_schedule, check_pass, make_schedule,
    reindex, run_pipeline)
from repro.comm.policy import (  # noqa: F401
    GreedyBandwidthPolicy, PathPolicy, RoundRobinPolicy, TunerPolicy,
    contention_scaled, make_policy)
from repro.comm.planner import PathPlanner  # noqa: F401
from repro.comm.cache import (  # noqa: F401
    CompiledPlan, FastPathCache, FastPathEntry, PlanLifecycle,
    TransferPlanCache, compile_plan)
from repro.comm.telemetry import (  # noqa: F401
    DispatchSample, StageTimings, TimelineRecorder)
from repro.comm.calibration import (  # noqa: F401
    PROFILE_VERSION, CalibrationFitter, CalibrationProfile,
    modeled_sample_time_s, modeled_vs_measured)
from repro.comm.collectives import (  # noqa: F401
    bidir_ring_all_gather, bidir_ring_reduce_scatter, modeled_all_reduce_s,
    multipath_all_reduce, multipath_all_to_all, psum_via_multipath,
    select_all_reduce_strategy, tier_bandwidths_gbps, two_level_all_reduce)
from repro.comm.health import (  # noqa: F401
    LADDER, CommFaultError, FaultEvent, FaultInjector, HealthMonitor,
    HealthStats, LinkFaultError)
from repro.comm.engine import (  # noqa: F401
    AXIS, GroupKey, MultiPathTransfer, group_signature,
    multipath_send_local, plan_signature)
from repro.comm.session import (  # noqa: F401
    BoundCollectives, CollectiveKey, CommSession)
