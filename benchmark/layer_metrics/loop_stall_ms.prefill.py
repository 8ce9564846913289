"""Milliseconds of 50 ms-or-longer host spans a second: ``long_self_us`` of
the loop thread's working spans over ``wall_us``, between the window's two
``engine.snapshot()["host"]``. The harness prints the traced run's reading, in
which the seconds between the two snapshots include the profiler's stop (as long
again as the window, or longer). Nothing where the snapshot has no ``host``.
Source: program_counter."""

from harness.host_counters import loop_stall_ms as read  # noqa: E402,F401
