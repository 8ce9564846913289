"""What the readers under ``benchmark/layer_metrics/`` share: each per-layer
metric has its own file there, found by its name, and takes its ``read``
from here where another metric (of this cell or of a later one) reads the
same quantity.
"""

from . import loadgen, peaks, work

FLASH_KERNEL = r"flash_attention"     # the jitted wrapper names the op


def _model(run):
    return run.size(run.cfg["runner_args"])["model"]


def _prompts(run):
    return [s for name, sizes in run.calls if name == "bench.prefill"
            for s in sizes]


def idle_share(run):
    if run.reduced is None:
        return None
    return 100.0 * run.reduced.idle_share()


def prefill_device_ms(run):
    red = run.reduced
    if red is None or not red.launches("bench.prefill"):
        return None
    return (red.device_ns_in("bench.prefill")
            / red.launches("bench.prefill") / 1e6)


def prefill_step_mfu(run):
    red = run.reduced
    if red is None or run.peak is None:
        return None
    secs = red.device_ns_in("bench.prefill") / 1e9
    flops = sum(work.prefill_flops(s, _model(run)) for s in _prompts(run))
    if not secs or not flops:
        return None
    return 100.0 * flops / secs / run.peak["bf16_flops"]


def flash_prefill_roofline(run):
    red = run.reduced
    if red is None or run.peak is None:
        return None
    ns, n = red.op_ns(FLASH_KERNEL)
    if not n:
        return None
    d, layers = _model(run)["d_model"], _model(run)["n_layers"]
    least = sum(layers * peaks.roofline_seconds(
        work.causal_attention_flops(s, d), 4 * s * d * work.F32, run.peak)[0]
        for s in _prompts(run))
    return 100.0 * least / (ns / 1e9) if least else None


def rpc_ttft_overhead_ms(run):
    rows = [1e3 * (r.frame_t[0] - r.t_sent) - r.engine_ttft_us / 1e3
            for r in run.window.get("sent", []) if r.finished and r.frame_t]
    return sum(rows) / len(rows) if rows else None


def ttft_percentile(run, q):
    sent = run.window.get("sent")
    return loadgen.percentile(loadgen.ttft_ms(sent), q) if sent else None
