"""Public facade for the OMP2MPI engine: ``from repro import omp``.

Mirrors the OpenMP surface the paper consumes:

* ``@omp.parallel_for(stop=N, schedule=omp.dynamic(), reduction={...})``
  annotates a loop body — the ``#pragma omp parallel for target mpi``.
* calling the resulting program runs the *shared-memory* semantics
  (the original OpenMP program);
* ``omp.compile(program, mesh, omp.Options(...))`` performs the
  source-to-source transformation through the staged pass pipeline
  (``analyze → schedule → plan → plan_comm → lower``) and returns the
  distributed ("MPI") program as a :class:`~repro.core.api.Compiled`
  artifact.  It accepts a single ``ParallelFor`` block or a whole
  ``ParallelRegion``.

``omp.to_mpi`` / ``omp.region_to_mpi`` are deprecated shims over
``omp.compile`` and emit ``DeprecationWarning``.
"""
from repro.core.api import (  # noqa: F401
    CommMode,
    Compiled,
    CompileError,
    Lowering,
    Options,
    PassRecord,
    ShardPolicy,
    clear_compile_cache,
    compile,
    compile_cache_stats,
    disable_persistent_cache,
    enable_persistent_cache,
)
from repro.core.aot_store import AOTStore  # noqa: F401
from repro.core.timing import stats as timing_stats  # noqa: F401
from repro.core.context import (  # noqa: F401
    Affine,
    ContextInfo,
    ReadKind,
    VarClass,
    WriteKind,
    analyze_context,
)
from repro.core.comm import (  # noqa: F401
    ALPHA_LAUNCH_BYTES,
    BoundaryComm,
    CommCost,
    halo_exchange,
    halo_exchange2,
    modeled_cost_bytes,
    plan_boundary,
    plan_boundary2,
    plan_comm,
)
from repro.core.comm_schedule import (  # noqa: F401
    CommEvent,
    CommGroup,
    CommSchedule,
    build_comm_schedule,
)
from repro.core.loop import LoopInfo, LoopNotCanonical, analyze_loop  # noqa: F401
from repro.core.nest import LoopNest, NestAffine, ShiftedWindow  # noqa: F401
from repro.core.plan import DistPlan, KAffine, make_plan  # noqa: F401
from repro.core.pragma import (  # noqa: F401
    DYNAMIC,
    GUIDED,
    STATIC,
    At,
    ParallelFor,
    ParallelRegion,
    Put,
    Red,
    Schedule,
    SerialStage,
    at,
    dynamic,
    guided,
    parallel_for,
    put,
    red,
    region,
    serial,
    static,
)
from repro.core.pallas_lower import (  # noqa: F401
    KernelPlan,
    KernelSpan,
)
from repro.core.region import (  # noqa: F401
    DistributedRegion,
    RegionPlan,
    SlabLayout,
    SlabLayout2,
    plan_region,
    region_to_mpi,
)
from repro.core.schedule import (  # noqa: F401
    ChunkPlan,
    guided_chunk_size,
    make_chunk_plan,
    make_nest_chunk_plans,
    paper_chunk_size,
)
from repro.core.transform import (  # noqa: F401
    DistributedProgram,
    run_reference,
    to_mpi,
)
