"""How much else ran in the loop thread's process, in % of one core: the
``process`` CPU less the ``serving`` role's, over ``wall_us``, between the
window's two ``engine.snapshot()["host"]["threads"]``. The harness prints the
traced run's reading: the profiler's own threads and its stop (both the CPU and
the seconds of it lie between the two snapshots) about triple it. Nothing where
the snapshot has no ``host``. Source: program_counter."""

from harness.host_counters import contender_cpu_share as read  # noqa: E402,F401
