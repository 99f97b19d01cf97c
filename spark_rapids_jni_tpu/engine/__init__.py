"""Query-plan engine: logical plan DAG, optimizer, executor, plan cache.

The layer Spark plays for the reference repo, grown natively: build a
``Scan/Filter/Project/Join/Aggregate/Sort/Limit`` DAG (plan.py), let
``optimize`` prune projections and push predicates into scan row-group
pruning (optimizer.py), then ``execute`` it on the ops/io layers
(executor.py): Filter/Project/Aggregate chains between breakers fuse into
single jitted segments cached by (fingerprint, shape-class) in
``SEGMENT_CACHE`` (segment.py), and chunked scans stream double-buffered —
a producer thread decodes+stages chunk k+1 while chunk k computes, partials
accumulating on device with no per-chunk sync.  Streamed probe joins ride
the same segments: a scan-independent build side is hashed + sorted once
per execution (``BUILD_CACHE``, cache.py) and enters the chunk program as
a pytree input; ``Limit(Sort(...))`` fuses into a ``TopK`` node executed
as a per-chunk partial top-k over order-preserving u64 keys.  ``PlanCache``
(cache.py) lets repeat queries skip optimization and hit the warm jit
caches.  Under concurrent serving (scheduler.py) N sessions run at once:
an SLO-aware admission controller queues or sheds past ``SRJT_MAX_SESSIONS``,
a deficit-round-robin gate interleaves their chunks at recovery
checkpoints, and ``RESULT_CACHE`` (cache.py) serves repeat plans over
unchanged input files without executing at all — ``docs/SERVING.md``.
``docs/ENGINE.md`` has the full design, including the bridge's one-message
``PLAN_EXECUTE`` wire format.
"""

from .plan import (  # noqa: F401
    Aggregate,
    Filter,
    Join,
    Limit,
    PlanNode,
    Project,
    Scan,
    Sort,
    TopK,
    col,
    deserialize,
    expr_columns,
    from_dict,
    lit,
    lit_date,
    lit_decimal,
    node_label,
)
from .optimizer import optimize, output_names  # noqa: F401
from .verify import (  # noqa: F401
    PlanVerificationError,
    SchemaResolver,
    verify,
)
from .physical import PhysicalPlan, lower  # noqa: F401
from .executor import execute, new_stats  # noqa: F401
from .cache import (  # noqa: F401
    BUILD_CACHE,
    RESULT_CACHE,
    BuildCache,
    CompiledPlan,
    PlanCache,
    ResultCache,
    data_version,
)
from .scheduler import (  # noqa: F401
    SCHEDULER,
    QuerySession,
    Scheduler,
)
from .explain import ExplainReport, explain_analyze  # noqa: F401
from .segment import (  # noqa: F401
    SEGMENT_CACHE,
    CompiledSegment,
    Segment,
    SegmentCache,
    build_segment,
    build_stream_segment,
)
