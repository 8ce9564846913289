"""The program's own spans in a traced run, and the device's idle time by
what the engine's loop thread was doing.

The program marks every layer boundary of the serving host path with one
primitive (``brpc_tpu/profiling/registry.py``): a
``jax.profiler.TraceAnnotation`` named ``brpc.<layer>.<phase>``, on the
profiler's clock beside the device's op line. ``trace_reduce.extract`` keeps
only the benchmark's own ``bench.*`` annotations, so this module reads the
run's xplane file a second time. Two stages, as there:

- ``extract(path)`` reads the host events whose name starts with ``brpc.``
  with their thread and ids (needs JAX);
- ``ProgramSpans(host, busy, lo, hi)`` is pure Python and is what
  ``benchmark/tests`` checks on hand-made lists. It finds the engine's loop
  thread (the one that holds ``brpc.engine.step``), cuts each thread's nested
  spans into leaf segments (at every instant the innermost open span) and
  intersects the first device's idle intervals with the loop thread's
  leaves by OVERLAP: a gap that straddles two leaves is split between them.

A program without these spans (the parent of the PR that added them) gives
an empty list, every reader returns None and the line leaves the metric out.
"""

from __future__ import annotations

import bisect
from collections import defaultdict
from typing import Dict, List, Tuple

PREFIX = "brpc."
STEP = "brpc.engine.step"
IDLE = "brpc.engine.idle"
SYNC = "brpc.model.sync"
UNNAMED = "(no span)"
EDGE = "(window edge)"

Segment = Tuple[float, float, str]


def extract(path: str) -> List[list]:
    """[[name, start_ns, dur_ns, thread, {id: value}], ...]: the ``brpc.*``
    events of every host thread; a thread is named by its place in the
    file, since the profiler calls every Python thread ``python``."""
    import jax

    host = []
    data = jax.profiler.ProfileData.from_file(path)
    for p, plane in enumerate(data.planes):
        if not plane.name.startswith("/host:"):
            continue
        for l, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith(PREFIX):
                    host.append([e.name, float(e.start_ns),
                                 float(e.duration_ns), f"{p}.{l}",
                                 {k: v for k, v in e.stats}])
    return host


def leaf_segments(spans: List[Segment]) -> List[Segment]:
    """The leaf segments of ONE thread's nested spans [(lo, hi, name)]:
    sorted, non-overlapping, each named by the innermost span open there. A
    child that outlasts its parent (clock jitter) is cut to it."""
    out: List[Segment] = []
    stack: List[list] = []     # [lo, hi, name, edge]: covered up to edge

    def close(upto: float) -> None:
        while stack and stack[-1][1] <= upto:
            _lo, hi, name, edge = stack.pop()
            if hi > edge:
                out.append((edge, hi, name))
            if stack:
                stack[-1][3] = max(stack[-1][3], hi)

    for lo, hi, name in sorted(spans, key=lambda s: (s[0], -s[1])):
        close(lo)
        if stack:
            hi = min(hi, stack[-1][1])
        if hi <= lo:
            continue
        if stack:
            if lo > stack[-1][3]:
                out.append((stack[-1][3], lo, stack[-1][2]))
            stack[-1][3] = max(stack[-1][3], lo)
        stack.append([lo, hi, name, lo])
    close(float("inf"))
    return sorted(out)


def gaps_of(busy: List[Tuple[float, float]], lo: float,
            hi: float) -> List[Tuple[float, float]]:
    """The idle intervals inside lo..hi, from merged busy intervals."""
    out, edge = [], lo
    for s, e in busy:
        if s > edge:
            out.append((edge, min(s, hi)))
        edge = max(edge, e)
        if edge >= hi:
            break
    if edge < hi:
        out.append((edge, hi))
    return [(s, e) for s, e in out if e > s]


def overlap(intervals: List[Tuple[float, float]],
            segments: List[Segment]) -> Tuple[Dict[str, float], float]:
    """Time of ``intervals`` (sorted, disjoint) by the segment that covers
    it, and the longest piece that no segment covers (under UNNAMED)."""
    by: Dict[str, float] = defaultdict(float)
    longest, j = 0.0, 0
    for s, e in intervals:
        while j < len(segments) and segments[j][1] <= s:
            j += 1
        k, edge = j, s
        while k < len(segments) and segments[k][0] < e:
            lo, hi, name = segments[k]
            if lo > edge:
                by[UNNAMED] += lo - edge
                longest = max(longest, lo - edge)
            by[name] += min(hi, e) - max(lo, edge)
            edge = max(edge, min(hi, e))
            k += 1
        if e > edge:
            by[UNNAMED] += e - edge
            longest = max(longest, e - edge)
    return dict(by), longest


class ProgramSpans:
    """The ``brpc.*`` spans of one traced window against the first device's
    busy intervals (``Reduced.busy[dev]``), all on the profiler's clock."""

    def __init__(self, host: List[list], busy: List[Tuple[float, float]],
                 lo: float, hi: float):
        self.lo, self.hi = lo, hi
        self.window_ns = hi - lo
        self.busy = busy
        self._busy_starts = [s for s, _e in busy]
        by_thread: Dict[str, List[tuple]] = defaultdict(list)
        for name, start, dur, thread, ids in host:
            # a span cut by the window's edge counts for its part inside
            s, e = max(start, lo), min(start + dur, hi)
            if e > s:
                by_thread[thread].append((s, e, name, ids))
        self.threads = dict(by_thread)
        loops = [t for t, evs in by_thread.items()
                 if any(n == STEP for _s, _e, n, _i in evs)]
        self.loop = loops[0] if loops else None
        self.leaves = {t: leaf_segments([(s, e, n) for s, e, n, _i in evs])
                       for t, evs in by_thread.items()}
        self.idle = gaps_of(busy, lo, hi)

    def __bool__(self) -> bool:
        return self.loop is not None

    # ---- the loop thread
    def loop_spans(self, name: str) -> List[tuple]:
        """(lo, hi, ids) of the loop thread's spans called ``name``."""
        return [(s, e, i) for s, e, n, i in self.threads.get(self.loop, [])
                if n == name]

    def loop_cover(self) -> float:
        """Share of the window that the loop thread's leaves cover."""
        return sum(e - s for s, e, _n in self.leaves[self.loop]) \
            / self.window_ns

    def busy_ns_in(self, lo: float, hi: float) -> float:
        """Device busy time inside lo..hi."""
        i = max(0, bisect.bisect_right(self._busy_starts, lo) - 1)
        total = 0.0
        while i < len(self.busy) and self.busy[i][0] < hi:
            total += max(0.0, min(self.busy[i][1], hi)
                         - max(self.busy[i][0], lo))
            i += 1
        return total

    # ---- idle time by the loop thread's leaf span
    def idle_by_leaf(self) -> Tuple[Dict[str, float], float]:
        """Idle time by the loop thread's leaf, and the longest piece in no
        span. The profiler records no span that is open when the session
        starts or stops, so what lies before the first leaf and after the
        last is the window's edge, named apart."""
        leaves = self.leaves[self.loop]
        first, last = leaves[0][0], leaves[-1][1]
        inner = [(max(s, first), min(e, last)) for s, e in self.idle
                 if min(e, last) > max(s, first)]
        by, longest = overlap(inner, leaves)
        edge = sum(e - s for s, e in self.idle) - sum(e - s for s, e in inner)
        if edge:
            by[EDGE] = edge
        return by, longest

    def idle_shares(self) -> Tuple[float, float]:
        """(waiting, working) in percent of the window: the device idle
        while the loop thread is in ``brpc.engine.idle`` (nothing to run:
        the request is in the RPC path or at the client), and while it is
        in any other leaf (the engine's own host work holds the chip)."""
        by, _longest = self.idle_by_leaf()
        waiting = by.get(IDLE, 0.0)
        working = sum(v for n, v in by.items()
                      if n not in (IDLE, UNNAMED, EDGE))
        return (100.0 * waiting / self.window_ns,
                100.0 * working / self.window_ns)

    def sync_idle(self) -> Dict[str, float]:
        """The idle time inside ``brpc.model.sync`` leaves, by where it
        lies in its launch: before the device's first op (``head``),
        between ops (``between``), after its last op (``tail``: the
        host's wake-up, where a contended interpreter shows)."""
        out = {"head": 0.0, "between": 0.0, "tail": 0.0}
        for lo, hi, name in self.leaves[self.loop]:
            if name != SYNC:
                continue
            i = max(0, bisect.bisect_right(self._busy_starts, lo) - 1)
            inside = []
            while i < len(self.busy) and self.busy[i][0] < hi:
                s, e = max(self.busy[i][0], lo), min(self.busy[i][1], hi)
                if e > s:
                    inside.append((s, e))
                i += 1
            if not inside:     # the device was done before the wait began
                out["tail"] += hi - lo
                continue
            out["head"] += inside[0][0] - lo
            out["tail"] += hi - inside[-1][1]
            out["between"] += sum(b[0] - a[1]
                                  for a, b in zip(inside, inside[1:]))
        return out

    # ---- every thread
    def leaf_ns(self, prefix: str) -> float:
        """Leaf time, over all threads, in spans whose name starts so."""
        return sum(e - s for segs in self.leaves.values()
                   for s, e, n in segs if n.startswith(prefix))

    def count(self, name: str, with_id: str = None) -> int:
        return sum(1 for evs in self.threads.values()
                   for _s, _e, n, ids in evs
                   if n == name and (with_id is None or with_id in ids))

    def table(self) -> List[str]:
        """The lines behind the idle attribution, for the run's log."""
        by, longest = self.idle_by_leaf()
        idle_ns = sum(e - s for s, e in self.idle)
        rows = [f"device idle {idle_ns / 1e9:.4f}s of "
                f"{self.window_ns / 1e9:.4f}s by the loop thread's leaf span "
                f"(leaves cover {100 * self.loop_cover():.2f}% of the window;"
                f" longest idle piece in no span {longest / 1e6:.3f} ms):"]
        for name, ns in sorted(by.items(), key=lambda kv: -kv[1]):
            rows.append(f"  {name:<26} {ns / 1e9:9.4f}s "
                        f"{100 * ns / self.window_ns:6.2f}% of the window")
        sync = self.sync_idle()
        rows.append("  within brpc.model.sync: " + ", ".join(
            f"{k} {v / 1e9:.4f}s" for k, v in sync.items()))
        return rows


def of(run):
    """The run's ProgramSpans, read once and kept on ``run``; None where
    there is no traced window or the program has no such spans."""
    if getattr(run, "program_spans", None) is None:
        if run.reduced is None:
            return None
        from . import common, trace_reduce

        red = run.reduced
        host = extract(trace_reduce.find_xplane(common.TRACE_DIR))
        run.program_spans = ProgramSpans(host, red.busy[red.devices[0]],
                                         red.lo, red.hi)
        if run.program_spans:
            for row in run.program_spans.table():
                run.say(row)
    return run.program_spans or None


# ------------------------------------------------------------------ readers
def idle_engine_waiting(run):
    spans = of(run)
    return spans.idle_shares()[0] if spans else None


def idle_engine_working(run):
    spans = of(run)
    return spans.idle_shares()[1] if spans else None


def queue_wait_ms(run):
    """Mean time between ``submit`` and admission over the sequences the
    engine admitted in the window: two ``snapshot()`` calls."""
    a, b = run.window.get("snap0"), run.window.get("snap1")
    if not a or not b or "queue_wait_us_sum" not in b \
            or b["admitted"] == a["admitted"]:
        return None
    return ((b["queue_wait_us_sum"] - a["queue_wait_us_sum"])
            / (b["admitted"] - a["admitted"]) / 1e3)
