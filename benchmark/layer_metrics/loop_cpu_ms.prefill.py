"""CPU time one engine step costs the loop thread: the ``serving`` role's CPU
time (the loop thread's own clock, ``host["threads"]``) less the CPU used inside
the loop's spans that wait by design (``host["waits"]``: idle, pool_wait, sync),
over the difference of ``steps``, between the window's two
``engine.snapshot()["host"]``. The harness prints the traced run's reading,
about twice the untraced one (the profiler's annotations). Nothing where the
snapshot has no ``host``. Source: program_counter."""

from harness.host_counters import loop_cpu_ms as read  # noqa: E402,F401
