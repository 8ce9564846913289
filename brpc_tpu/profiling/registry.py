"""Thread-role registry and the program's one span primitive.

The statistical profiler (profiling/sampler.py) reads stacks of *other*
threads via ``sys._current_frames()``; to attribute a sample it needs two
facts the frame graph cannot tell it:

- **role** — what kind of thread this is (poller/worker/timer/healer/...),
  registered once at thread creation by the spawning code, and
- **phase** — which span the thread is executing *right now*
  (``rpc.parse``/``rpc.execute``/``engine.prefill``/``model.sync``/...),
  stamped at every layer boundary of the request path by :class:`span`
  and, on the dispatch fast paths, by :func:`set_phase`.

A span does three things at one boundary: it stamps the thread's phase
(the sampler reads the name's last component: ``parse``, ``prefill``), it
enters a ``jax.profiler.TraceAnnotation("brpc.<name>", **ids)`` so the
span lies on the profiler's own clock beside the device's op line, and it
adds its elapsed time to the thread's per-name counters. There is no
switch: with no profiler session the annotation is a no-op, and before
``jax`` is imported it is skipped (this module never imports ``jax``).

Spans nest; :func:`set_phase` keeps ONE span open per nesting level and
swaps it, so a thread's timeline is flat there: at every instant exactly
one innermost ("leaf") span, which is what attributing a device gap to
the host needs.

Phases live in a plain dict keyed by thread ident: writes are single dict
stores under the GIL (atomic, no lock), reads from the sampler race
benignly — a stale phase misattributes at most one 1/hz sample. A
``threading.local`` would not work here because the sampler must read the
marker from *outside* the marked thread. The span counters are written by
their own thread only.

This module intentionally imports nothing beyond the standard library so
the hot dispatch paths can stamp phases without dragging in the sampler
machinery (or ``jax``).
"""

from __future__ import annotations

import sys
import threading
from time import perf_counter_ns
from typing import Dict, List, Optional

get_ident = threading.get_ident

# role vocabulary (free-form strings are accepted; these are the ones the
# framework registers)
ROLE_POLLER = "poller"      # event dispatcher / native poller / shm cut loop
ROLE_WORKER = "worker"      # fiber workers (user code runs here)
ROLE_TIMER = "timer"        # fiber timer thread
ROLE_HEALER = "healer"      # tunnel heal / health-check probes
ROLE_BATCH = "batch"        # device-lane batch dispatch
ROLE_SAMPLER = "sampler"    # bvar sampler + the profiler itself
ROLE_USER = "user"          # anything unregistered (main thread, app threads)

_roles: Dict[int, str] = {}
_phases: Dict[int, str] = {}

# process-wide role prefix: shard worker processes set "worker:<i>/" once
# at startup so every role they register — and the unregistered default —
# carries the worker identity when folded stacks are merged parent-side
_role_prefix = ""


def set_role_prefix(prefix: str) -> None:
    global _role_prefix
    _role_prefix = prefix


# ------------------------------------------------------------------- roles
def register_current_thread(role: str) -> None:
    """Tag the calling thread with a role; call first thing in run()."""
    _roles[get_ident()] = _role_prefix + role


def unregister_current_thread() -> None:
    ident = get_ident()
    _roles.pop(ident, None)
    _phases.pop(ident, None)
    _threads.pop(ident, None)


def role_of(ident: int) -> str:
    role = _roles.get(ident)
    return role if role is not None else _role_prefix + ROLE_USER


def threads_by_role() -> Dict[str, int]:
    """Live-thread counts keyed by role (for /status vitals)."""
    counts: Dict[str, int] = {}
    for th in threading.enumerate():
        role = _roles.get(th.ident, ROLE_USER) if th.ident else ROLE_USER
        counts[role] = counts.get(role, 0) + 1
    return counts


# ------------------------------------------------------------------- spans
class _ThreadSpans:
    """One thread's span state; only that thread writes it."""

    __slots__ = ("stats", "child_ns", "flat")

    def __init__(self):
        # name -> [count, total_ns, self_ns]; self is total minus the
        # spans closed inside it, so self times partition the thread's time
        self.stats: Dict[str, List[int]] = {}
        self.child_ns = 0   # time of the spans closed so far in the open one
        # the span set_phase holds open at this nesting level:
        # (name, annotation, t0, child_ns beneath it, marker beneath it)
        self.flat = None


_threads: Dict[int, _ThreadSpans] = {}
_annotation_names: Dict[str, str] = {}   # "rpc.parse" -> "brpc.rpc.parse"
_trace_annotation = None   # jax.profiler.TraceAnnotation, once jax is there


def _spans_of(ident: int) -> _ThreadSpans:
    st = _threads.get(ident)
    if st is None:
        st = _threads[ident] = _ThreadSpans()
    return st


def _annotate(name: str, ids: dict):
    """Enter the profiler annotation of span ``name`` (the one place the
    program does), or None while ``jax`` has not been imported."""
    global _trace_annotation
    cls = _trace_annotation
    if cls is None:
        cls = getattr(sys.modules.get("jax.profiler"), "TraceAnnotation",
                      None)
        if cls is None:
            return None
        _trace_annotation = cls
    full = _annotation_names.get(name)
    if full is None:
        full = _annotation_names[name] = "brpc." + name
    ann = cls(full, **ids)
    ann.__enter__()
    return ann


def _close(st: _ThreadSpans, name: str, ann, t0: int, outer_child: int) -> int:
    """Leave a span: clock, annotation, counters, the parent's child time."""
    dt = perf_counter_ns() - t0
    if ann is not None:
        ann.__exit__(None, None, None)
    rec = st.stats.get(name)
    if rec is None:
        rec = st.stats[name] = [0, 0, 0]
    rec[0] += 1
    rec[1] += dt
    rec[2] += dt - st.child_ns
    st.child_ns = outer_child + dt
    return dt


class span:
    """``with span("engine.prefill", seq=7, n=512) as sp: ...`` — phase
    marker, profiler annotation and counters at one boundary (see the
    module docstring). ``ids`` are one or two integers that tie a request's
    spans together. ``sp.elapsed_ns`` holds the duration after the exit,
    so a caller that reports it reads no clock of its own."""

    __slots__ = ("name", "ids", "elapsed_ns", "_st", "_prev", "_outer",
                 "_ann", "_t0")

    def __init__(self, name: str, **ids):
        self.name = name
        self.ids = ids
        self.elapsed_ns = 0

    def __enter__(self):
        ident = get_ident()
        st = self._st = _spans_of(ident)
        self._prev = _phases.get(ident)
        _phases[ident] = self.name
        self._outer = (st.child_ns, st.flat)
        st.child_ns = 0
        st.flat = None
        self._ann = _annotate(self.name, self.ids)
        self._t0 = perf_counter_ns()
        return self

    def note(self, **ids) -> None:
        """Ids known only once the work is done (how many were admitted)."""
        if self._ann is not None:
            self._ann.set_metadata(**ids)

    def __exit__(self, *exc):
        st = self._st
        if st.flat is not None:   # a set_phase left open inside this span
            _close(st, *st.flat[:4])
        outer_child, st.flat = self._outer
        self.elapsed_ns = _close(st, self.name, self._ann, self._t0,
                                 outer_child)
        if self._prev is None:
            _phases.pop(get_ident(), None)
        else:
            _phases[get_ident()] = self._prev
        return False


phase = span   # the older name of the context manager


def set_phase(name: Optional[str], **ids) -> Optional[str]:
    """The dispatch fast paths' form of :class:`span`: leave the span this
    thread's last ``set_phase`` opened and enter ``name`` in its place, so
    consecutive phases lie side by side, never inside one another. Returns
    the previous marker; handing that back (or None) restores it without
    opening a span — the enclosing :class:`span`, if any, is still open."""
    ident = get_ident()
    prev = _phases.get(ident)
    st = _spans_of(ident)
    flat = st.flat
    if flat is not None:
        base = flat[4]
        st.flat = None
        _close(st, *flat[:4])
    else:
        base = prev
    if name is None:
        if prev is not None:
            del _phases[ident]
    else:
        _phases[ident] = name
        if name != base:
            outer_child, st.child_ns = st.child_ns, 0
            st.flat = (name, _annotate(name, ids), perf_counter_ns(),
                       outer_child, base)
    return prev


def phase_of(ident: int) -> Optional[str]:
    """The sampler's view of a thread's phase: the innermost open span's
    name without its layer (``rpc.parse`` and a bare ``parse`` both read
    ``parse``)."""
    name = _phases.get(ident)
    return name.rpartition(".")[2] if name else name


def thread_spans() -> Dict[str, List[int]]:
    """The calling thread's span counters, ``{name: [count, total_ns,
    self_ns]}`` — the live dict, so the thread's owner can keep reading it
    (``ServingEngine.snapshot``) after the thread has gone."""
    return _spans_of(get_ident()).stats


# ----------------------------------------------------------------- hygiene
def prune(live_idents) -> None:
    """Drop registry entries for dead thread idents (idents are reused by
    the OS; the sampler calls this with sys._current_frames() keys, which
    cover every live thread)."""
    live = set(live_idents)
    for d in (_roles, _phases, _threads):
        for ident in [i for i in d if i not in live]:
            d.pop(ident, None)


def reset_for_test() -> None:
    global _role_prefix
    _roles.clear()
    _phases.clear()
    _threads.clear()
    _role_prefix = ""
