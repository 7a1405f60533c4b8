"""Performance subsystem: shape-stable execution + host↔device overlap.

Three cooperating pieces (see each module's docstring):

- ``bucketing``     — BucketPolicy / pad_to_bucket / unpad / pad_dataset:
                      canonical batch shapes so XLA compiles once per bucket,
                      not once per batch size;
- ``prefetch``      — DevicePrefetchIterator: double-buffered, sharding-aware
                      device placement of batch N+1 while step N runs;
- ``compile_watch`` — CompileWatch: compile/dispatch counters so tests and
                      benches can assert "N batches, 1 compile";
- ``compile_cache`` — persisted XLA compilation cache for serving cold
                      starts (second bring-up replays executables from
                      disk), with an observable cache-hit counter;
- ``fusion``        — fuse/fuse_network (Conv→BN→Act fused blocks with a
                      memory-efficient custom VJP — 2-D, separable and 1-D
                      heads), fold_bn (inference-time BN folding, residual
                      blocks included), remat policies, and the
                      jaxpr-derived training_activation_bytes measurement;
- ``planner``       — plan_memory: fit training under a stated HBM budget
                      by searching fusion + per-layer remat against the
                      measured residual set (predict → verify;
                      BudgetInfeasibleError when nothing fits);
- ``autotune``      — compile-time autotuner over batch size / fusion /
                      donation / bucket ladders using
                      jit(...).lower().compile().cost_analysis(), emitting
                      a persisted TuningRecord that training replicas and
                      serving endpoints inherit.
"""

from deeplearning4j_tpu.perf.bucketing import (  # noqa: F401
    BucketPadDataSetIterator,
    BucketPolicy,
    pad_dataset,
    pad_multi_dataset,
    pad_to_bucket,
    unpad,
)
from deeplearning4j_tpu.perf.compile_cache import (  # noqa: F401
    cache_hits,
    enable_compilation_cache,
)
from deeplearning4j_tpu.perf.compile_watch import (  # noqa: F401
    GLOBAL as GLOBAL_COMPILE_WATCH,
    CompileWatch,
)
from deeplearning4j_tpu.perf.fusion import (  # noqa: F401
    REMAT_POLICIES,
    fold_bn,
    fuse,
    fuse_network,
    remat_policy,
    training_activation_bytes,
)
from deeplearning4j_tpu.perf.prefetch import DevicePrefetchIterator  # noqa: F401
from deeplearning4j_tpu.perf.planner import (  # noqa: F401
    BudgetInfeasibleError,
    MemoryPlan,
    PlanError,
    plan_memory,
)
from deeplearning4j_tpu.perf.autotune import (  # noqa: F401
    StaleTuningRecordError,
    TuningRecord,
    apply_tuning,
    autotune,
    build_network,
    conf_signature,
    verify_tuning,
)
