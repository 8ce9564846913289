"""From the profiler's trace to numbers: the one reduction every PR shares.

Two stages. ``extract(path)`` reads an ``.xplane.pb`` (with JAX alone) into
plain lists: per device the events of its op line, and the host's
``bench.*`` annotations. ``Reduced(events)`` turns those lists into busy
and idle time, device time per annotation, kernel time by name, collective
time and the idle gaps by what the host was in. The second stage is pure
Python and is what ``benchmark/tests`` checks against a recorded trace.

Clock: every time is in nanoseconds on the profiler's own clock, on which
the device lines and the host's annotations both lie. Every served launch
blocks its caller until the device is done (``int(nxt)`` /
``np.asarray(nxt)``), so a launch's device work lies inside its
annotation, and is attributed by where each op's mid-point falls.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, Tuple

OP_LINE = "XLA Ops"
WINDOW = "bench.window"
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|all-to-all|reduce-scatter|collective-permute"
    r"|collective-broadcast|ppermute|\bsend\b|\brecv\b|send-done|recv-done")


def short_name(name: str) -> str:
    """The op line names an event by its whole HLO text
    (``%fusion.12 = f32[...] fusion(...)``): keep the instruction's name."""
    return name.split(" = ", 1)[0].lstrip("%")


def group_name(name: str) -> str:
    """An instruction's name without its number: the twelve layers' copies
    of one fusion (``fusion.12``, ``fusion.13``) read as one row."""
    return re.sub(r"[._]+\d+$", "", name)


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def extract(path: str, platform: str = "tpu") -> dict:
    """{"devices": {id: [[start_ns, dur_ns, name], ...]},
        "host": [[name, start_ns, dur_ns, thread], ...]}"""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    devices: Dict[str, list] = {}
    host: List[list] = []
    dev_re = re.compile(rf"^/device:{platform.upper()}:(\d+)$")
    for plane in data.planes:
        m = dev_re.match(plane.name)
        if m:
            for line in plane.lines:
                if line.name == OP_LINE:
                    devices[m.group(1)] = [
                        [float(e.start_ns), float(e.duration_ns),
                         short_name(e.name)] for e in line.events]
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                if platform == "cpu" and line.name.startswith("tf_XLA"):
                    # a CPU rehearsal: the XLA client's threads stand in for
                    # a device line, to walk the code (never a result)
                    devices.setdefault("0", []).extend(
                        [float(e.start_ns), float(e.duration_ns), e.name]
                        for e in line.events
                        if e.duration_ns > 0
                        and not e.name.startswith(("Threadpool", "end:")))
                for e in line.events:
                    if e.name.startswith("bench."):
                        host.append([e.name, float(e.start_ns),
                                     float(e.duration_ns), line.name])
    return {"devices": devices, "host": host}


# ------------------------------------------------------------------ intervals
def union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Sorted, merged, non-overlapping [start, end) intervals."""
    out: List[Tuple[float, float]] = []
    for s, e in sorted(i for i in intervals if i[1] > i[0]):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def total(intervals) -> float:
    return sum(e - s for s, e in intervals)


def self_times(ops: List[list]) -> List[Tuple[float, float, str, float]]:
    """(start, end, name, self_ns) per op of one line: an op that encloses
    others on its line (a ``while`` around its body) keeps only the time
    its children do not cover."""
    evs = sorted(((s, s + d, n) for s, d, n in ops),
                 key=lambda t: (t[0], -t[1]))
    out, stack = [], []     # stack of [start, end, name, child_ns]

    def close(upto):
        while stack and stack[-1][1] <= upto:
            s, e, n, child = stack.pop()
            out.append((s, e, n, max(0.0, (e - s) - child)))
            if stack:
                stack[-1][3] += e - s

    for s, e, n in evs:
        close(s)
        if stack and e > stack[-1][1]:
            e = stack[-1][1]        # a child never outlasts its parent
        stack.append([s, e, n, 0.0])
    close(float("inf"))
    return out


class Reduced:
    """The numbers of one traced window."""

    def __init__(self, events: dict):
        host = events["host"]
        wins = [(s, s + d) for n, s, d, _t in host if n == WINDOW]
        dev_ops = events["devices"]
        if wins:
            self.lo, self.hi = wins[0]
        else:
            pts = [(s, s + d) for ops in dev_ops.values() for s, d, _ in ops]
            self.lo = min(p[0] for p in pts)
            self.hi = max(p[1] for p in pts)
        self.window_s = (self.hi - self.lo) / 1e9
        # host spans other than the window that lie wholly inside it
        self.spans = sorted((s, s + d, n) for n, s, d, _t in host
                            if n != WINDOW and self.lo <= s and
                            s + d <= self.hi)
        self._span_starts = [s for s, _e, _n in self.spans]
        self.devices = sorted(dev_ops, key=int)
        self.ops = {}          # device -> [(start, end, name, self_ns)]
        self.busy = {}         # device -> merged busy intervals in window
        for dev in self.devices:
            inside = [o for o in dev_ops[dev]
                      if o[0] >= self.lo and o[0] + o[1] <= self.hi]
            self.ops[dev] = self_times(inside)
            self.busy[dev] = union([(s, e) for s, e, _n, _x in self.ops[dev]])

    # ---- busy and idle
    def busy_s(self, dev: str) -> float:
        return total(self.busy[dev]) / 1e9

    @property
    def busy_mean_s(self) -> float:
        return sum(self.busy_s(d) for d in self.devices) / len(self.devices)

    def idle_share(self, worst: bool = True) -> float:
        shares = [1.0 - self.busy_s(d) / self.window_s for d in self.devices]
        return max(shares) if worst else sum(shares) / len(shares)

    # ---- spans
    def span_at(self, t: float) -> str:
        i = bisect.bisect_right(self._span_starts, t) - 1
        if i >= 0 and self.spans[i][0] <= t < self.spans[i][1]:
            return self.spans[i][2]
        return ""

    def launches(self, name: str) -> int:
        return sum(1 for _s, _e, n in self.spans if n == name)

    def device_ns_in(self, name: str, dev: str = None) -> float:
        """Device op time (self time, so nothing counts twice) whose
        mid-point lies inside a span called ``name``."""
        dev = dev or self.devices[0]
        return sum(x for s, e, _n, x in self.ops[dev]
                   if self.span_at((s + e) / 2) == name)

    def unattributed_share(self, dev: str = None) -> float:
        dev = dev or self.devices[0]
        allt = sum(x for _s, _e, _n, x in self.ops[dev])
        out = sum(x for s, e, _n, x in self.ops[dev]
                  if not self.span_at((s + e) / 2))
        return out / allt if allt else 0.0

    # ---- kernels and collectives
    def op_ns(self, pattern: str, dev: str = None) -> Tuple[float, int]:
        """(self time, count) of the ops whose name matches ``pattern``."""
        rx = re.compile(pattern)
        dev = dev or self.devices[0]
        hit = [x for _s, _e, n, x in self.ops[dev] if rx.search(n)]
        return sum(hit), len(hit)

    def collective_ns(self, dev: str) -> float:
        """Time in which a collective occupies the device's op line: ops
        on that line run one at a time, so no compute runs then."""
        return sum(x for _s, _e, n, x in self.ops[dev]
                   if COLLECTIVE.search(n))

    # ---- breakdown
    def top_ops(self, k: int = 10) -> List[list]:
        """The first device's op time by instruction name, numbers off."""
        by = defaultdict(float)
        dev = self.devices[0]
        for _s, _e, n, x in self.ops[dev]:
            by[group_name(n)] += x
        return [[n, ns / 1e9] for n, ns in
                sorted(by.items(), key=lambda kv: -kv[1])[:k]]

    def idle_gaps(self, k: int = 10) -> List[list]:
        """Idle time of the first device by what the host was in at the
        middle of each gap: inside which annotation, or between calls."""
        dev = self.devices[0]
        by = defaultdict(float)
        edge = self.lo
        for s, e in self.busy[dev] + [(self.hi, self.hi)]:
            if s > edge:
                where = self.span_at((edge + s) / 2)
                by["in " + where if where else "between calls"] += s - edge
            edge = max(edge, e)
        return [[n, ns / 1e9] for n, ns in
                sorted(by.items(), key=lambda kv: -kv[1])[:k]]
