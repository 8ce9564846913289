"""Time from a request's DUE time to its first token frame in an open-loop
cell, median over the window's requests. It is what a user feels first, but
a request waits out the decode step in flight when it arrives, so at 75
requests a window the runs of ANY statistic of it spread by 12% and more
(PERF.md, PR 25): it stands here without a bound, a part of the bounded
``answer_mean_ms`` that the scheduler's admission decides. Source: host_clock."""

from harness.readers import ttft_percentile


def read(run):
    return ttft_percentile(run, 50)
