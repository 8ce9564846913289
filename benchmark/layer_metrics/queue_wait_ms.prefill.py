"""Mean time a sequence waits in ``ServingEngine``'s queue, ``submit`` to
admission: the difference of ``queue_wait_us_sum`` over that of ``admitted``
between two ``ServingEngine.snapshot()`` calls. Source: program_counter."""

from harness.program_spans import queue_wait_ms as read  # noqa: E402,F401
