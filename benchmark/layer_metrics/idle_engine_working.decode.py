"""Share of the traced window in which the first device is idle AND the
engine's loop thread is inside any leaf span but ``brpc.engine.idle`` (admit,
prep, launch, sync, commit, reap, pool_wait, the stream write): the engine's
own host work holds the chip. With ``idle_engine_waiting`` it adds up to
``idle_share`` less what no span covers. Source: program_span."""

from harness.program_spans import idle_engine_working as read  # noqa: E402,F401
