"""brpc_tpu.profiling — whole-process statistical profiler.

- registry: thread-role registry + the span primitive (phase marker,
  profiler annotation and counters at one boundary; import this directly
  from hot paths; it has no dependencies)
- sampler: sys._current_frames() folded-stack sampler (one-shot,
  start/stop session, always-on continuous ring)
- diff: folded-profile differ (top self-time movers)
"""

from brpc_tpu.profiling.registry import (  # noqa: F401
    ROLE_BATCH, ROLE_HEALER, ROLE_POLLER, ROLE_SAMPLER, ROLE_TIMER,
    ROLE_USER, ROLE_WORKER, cpu_by_role, gc_pauses, phase_of,
    register_current_thread, role_of, set_phase, span, spans_by_role,
    thread_spans, thread_waits, threads_by_role, unregister_current_thread,
    wait_span)
from brpc_tpu.profiling.sampler import (  # noqa: F401
    ContinuousProfiler, FoldedProfile, ProfileSession, collapse,
    continuous, ensure_continuous_started, run_profile)
