"""What one chunk of a long prompt adds to the gap of every live row: the
mean length of the loop thread's ``brpc.engine.prefill`` spans in the traced
window that prefilled PART of a prompt (``start > 0``, or fewer rows ``n``
than the prompt has, ``of``): the step's decode launch waits that long. Whole
spans only. Nothing where no prompt was chunked, or the program's spans carry
no ``start``. Source: program_span."""

from harness import program_spans


def read(run):
    spans = program_spans.of(run)
    if not spans:
        return None
    chunks = [hi - lo
              for lo, hi, ids in spans.loop_spans("brpc.engine.prefill")
              if "start" in ids and spans.lo < lo and hi < spans.hi
              and (int(ids["start"]) > 0 or int(ids["n"]) < int(ids["of"]))]
    return sum(chunks) / len(chunks) / 1e6 if chunks else None
