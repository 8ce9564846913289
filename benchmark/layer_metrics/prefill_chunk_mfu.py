"""The prefill launches' share of the chip's peak, chunks and whole prompts
alike: the MATMUL operations of the traced window's prefill launches (every
projection and MLP over the launch's rows, each attention layer's rows over
the context before and among them, the head once a prompt:
``prefill_chunk_flops`` of the run's block, from the rows ``n`` and the first
row ``start`` of the program's own ``brpc.model.prefill`` spans; the benchmark's
call log records ``len(tokens)``, which for a chunk is its END) over the
device's busy time inside the ``bench.prefill`` annotations that hold those
spans, over the bf16 peak. Pads and the scan's elementwise work are not
counted, so the share says how much of the chip's matmul rate the whole
prefill step, scans included, reaches. A launch ends its prompt where the
``brpc.engine.prefill`` span around it says so (``start + n == of``).
Nothing where the program's spans carry no ``start`` (a program without
chunked prefill) or the block counts no chunk. Source: device_trace (time)
and program_span (sizes)."""

from harness import program_spans


def read(run):
    red, spans = run.reduced, program_spans.of(run)
    flops_of = getattr(run.block, "prefill_chunk_flops", None)
    if red is None or run.peak is None or not spans or flops_of is None:
        return None
    model = run.size(run.cfg["runner_args"])["model"]
    engine = [(lo, hi, ids)
              for lo, hi, ids in spans.loop_spans("brpc.engine.prefill")
              if "of" in ids]
    marks = [(lo, hi) for lo, hi, name in red.spans if name == "bench.prefill"]
    flops = 0
    for lo, hi, ids in spans.loop_spans("brpc.model.prefill"):
        if "start" not in ids or not any(a <= lo and hi <= b
                                         for a, b in marks):
            continue      # no chunk's sizes, or outside the timed launches
        n, start = int(ids["n"]), int(ids["start"])
        around = [i for a, b, i in engine if a <= lo and hi <= b]
        ends = not around or start + n == int(around[0]["of"])
        flops += flops_of(n, start, model, ends)
    secs = red.device_ns_in("bench.prefill") / 1e9
    if not secs or not flops:
        return None
    return 100.0 * flops / secs / run.peak["bf16_flops"]
