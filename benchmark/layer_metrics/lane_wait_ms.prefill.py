"""Mean wait of a request between the lane thread that parsed its frame and
the poller that picked it up in Python: ``wait_us`` over ``events`` of
``engine.snapshot()["host"]["lane_wait"]["request"]`` between the window's
two snapshots. Nothing where the snapshot has no ``host``. Source:
program_counter."""

from harness.host_counters import lane_wait_ms as read  # noqa: E402,F401
