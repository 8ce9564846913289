"""Time the host is busy in the RPC layer per request: the leaf time of all
``brpc.rpc.*`` spans (parse, execute, respond, send, the stream write, the
client's call and its response and frame callbacks) on every thread inside
the traced window, over the requests answered in it (``brpc.rpc.respond``
spans that carry a correlation id). Source: program_span."""

from harness import program_spans


def read(run):
    spans = program_spans.of(run)
    if not spans:
        return None
    answered = spans.count("brpc.rpc.respond", with_id="cid")
    return spans.leaf_ns("brpc.rpc.") / answered / 1e6 if answered else None
