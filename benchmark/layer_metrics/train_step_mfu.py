"""The whole train step's share of the chips' peak: 6 x matmul parameters
per token plus causal attention forward and backward
(``work.train_step_flops``; recomputation not counted) for the steps of the
traced window, over the window's seconds, over chips x the bf16 peak. Held to
the FLOPs peak. Source: device_trace (the window) and the step count."""

from harness import work


def read(run):
    red, tr = run.reduced, getattr(run, "train", None)
    if red is None or run.peak is None or tr is None:
        return None
    flops = run.traced_steps * work.train_step_flops(
        tr["batch"], tr["seq"], tr["model"])
    return 100.0 * flops / red.window_s / (tr["chips"]
                                           * run.peak["bf16_flops"])
