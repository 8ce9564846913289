"""Host time of a decode step: over the ``brpc.engine.step`` spans of the
traced window that hold a ``brpc.model.decode``, the mean of the span's
duration less the first device's busy time inside it. With
``decode_device_ms`` it adds up to the step. Source: program_span."""

from harness import program_spans


def read(run):
    spans = program_spans.of(run)
    if not spans:
        return None
    decodes = spans.loop_spans("brpc.model.decode")
    steps = [(lo, hi) for lo, hi, _ids in spans.loop_spans("brpc.engine.step")
             if spans.lo < lo and hi < spans.hi     # whole steps only
             and any(lo <= d[0] and d[1] <= hi for d in decodes)]
    if not steps:
        return None
    return sum(hi - lo - spans.busy_ns_in(lo, hi)
               for lo, hi in steps) / len(steps) / 1e6
