"""Highest ``blocks_used / blocks_total`` of ``kv.snapshot()``, sampled every
50 ms of the window by a thread of the benchmark. Source: program_counter."""


def read(run):
    peak = run.window.get("kv_peak_share")
    return 100.0 * peak if peak else None
