"""Time a finished sequence's blocks take to enter the prefix cache: the
time inside the loop thread's ``brpc.engine.prefix_commit`` spans (the
radix tree's insert, its trim back under the watermark and the evictions
that takes) in the traced window, over their count; a span cut by the
window's edge counts for its part inside. Source: program_span."""

from harness import program_spans


def read(run):
    spans = program_spans.of(run)
    if not spans:
        return None
    commits = spans.loop_spans("brpc.engine.prefix_commit")
    if not commits:
        return None
    return sum(hi - lo for lo, hi, _ids in commits) / len(commits) / 1e6
