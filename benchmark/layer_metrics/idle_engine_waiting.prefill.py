"""Share of the traced window in which the first device is idle AND the
engine's loop thread is inside ``brpc.engine.idle``: the engine has nothing
to run, the request is in the RPC path or at the client. Idle intervals
(from the op line) are cut by the loop thread's leaf spans by overlap.
Source: program_span."""

from harness.program_spans import idle_engine_waiting as read  # noqa: E402,F401
