"""What the RPC and host path add to the first token: the client's time from
SEND to the first ``TokenDelta`` frame, minus the engine's own
``GenerateResponse.ttft_us`` (submit to first token), mean over the window's
finished requests. Source: host_clock at the client, program_counter
(``ttft_us``) at the engine."""

from harness.readers import rpc_ttft_overhead_ms as read  # noqa: E402,F401
