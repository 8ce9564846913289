"""Share of the engine's step time that goes to prefilling admitted
sequences: time in ``brpc.engine.prefill`` spans over time in
``brpc.engine.step`` spans on the loop thread, inside the traced window.
Source: program_span."""

from harness import program_spans


def read(run):
    spans = program_spans.of(run)
    if not spans:
        return None
    step = sum(hi - lo for lo, hi, _ids in spans.loop_spans("brpc.engine.step"))
    prefill = sum(hi - lo
                  for lo, hi, _ids in spans.loop_spans("brpc.engine.prefill"))
    return 100.0 * prefill / step if step else None
