"""The whole decode step's share of the chip's roofline: for each decode
launch of the traced window the least time the chip could take -- the larger
of its operations over the bf16 peak and its bytes (every weight once, plus
the LIVE K/V rows of each sequence; ``work.decode_step_*``) over the HBM peak
-- summed, over the device time inside ``bench.decode`` annotations. At these
batches the bytes bound it. Source: device_trace and the call log."""

from harness import peaks, work


def read(run):
    red = run.reduced
    if red is None or run.peak is None:
        return None
    secs = red.device_ns_in("bench.decode") / 1e9
    model = run.size(run.cfg["runner_args"])["model"]
    least = sum(peaks.roofline_seconds(work.decode_step_flops(ctx, model),
                                       work.decode_step_bytes(ctx, model),
                                       run.peak)[0]
                for name, ctx in run.calls if name == "bench.decode")
    if not secs or not least:
        return None
    return 100.0 * least / secs
