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
adds its elapsed time (``perf_counter_ns``) to the thread's per-name
counters. There is no switch: with no profiler session the annotation is a
no-op, and before ``jax`` is imported it is skipped (this module never
imports ``jax``).

A thread's CPU clock (``thread_time_ns``) is a system call, a third of a
microsecond on a plain Linux host and six on a sandboxed one, where it
also ticks at 10 ms, so no plain span reads it. A :class:`wait_span`, one
that waits for something BY DESIGN (nothing to run, a full pool, the
device), reads it at its two ends and keeps the CPU time used inside it.
The thread's CPU time (:func:`cpu_by_role`) less that of its waits is the
CPU its working spans cost, and their wall time less that is how long
they stood off the CPU.

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

Beside the spans the module answers, at snapshot time only, who in the
process was on the CPU: :func:`cpu_by_role` reads every live thread's CPU
clock and sums by role, :func:`spans_by_role` sums every thread's span
counters by role, and one ``gc.callbacks`` hook counts the collector's
pauses (:func:`gc_pauses`). A thread that ends leaves its numbers in
per-role retired sums, so two snapshots difference across its end.

This module intentionally imports nothing beyond the standard library so
the hot dispatch paths can stamp phases without dragging in the sampler
machinery (or ``jax``).
"""

from __future__ import annotations

import gc
import sys
import threading
from time import (clock_gettime_ns, perf_counter_ns, process_time_ns,
                  thread_time_ns)
from typing import Dict, List, Optional

get_ident = threading.get_ident
get_native_id = threading.get_native_id

# a close whose SELF wall time reaches this counts as long: a maximum cannot
# be differenced between two snapshots, a count and a sum can
LONG_SELF_NS = 50_000_000


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
# the same roles by the kernel's thread id: an ``ident`` goes to the next
# thread at once, with whatever role its last owner left there, and the CPU
# table must not read a new thread under a dead one's role
_roles_by_nid: Dict[int, str] = {}

# process-wide role prefix: shard worker processes set "worker:<i>/" once
# at startup so every role they register — and the unregistered default —
# carries the worker identity when folded stacks are merged parent-side
_role_prefix = ""


def set_role_prefix(prefix: str) -> None:
    global _role_prefix
    _role_prefix = prefix


# ------------------------------------------------------------------- roles
class _AtThreadEnd:
    """Held in a ``threading.local`` by every thread that took a role or
    opened a span: the interpreter drops a thread's locals as the thread
    ends, IN that thread, so the finalizer can still read the thread's own
    CPU clock. It is what makes a thread that was born and ended between
    two snapshots (a benchmark's closed-loop client) count at all."""

    __slots__ = ()

    def __del__(self):
        try:
            unregister_current_thread()
            _watched.discard(get_native_id())
        except Exception:   # the interpreter is going down with the thread
            pass


_local = threading.local()
# native ids of the threads that hold one and have yet to end: a thread
# leaves ``threading.enumerate()`` a moment BEFORE its locals are dropped,
# and cpu_by_role must not fold it in between, or it would fold it twice
_watched: set = set()


def _watch_thread_end() -> None:
    if getattr(_local, "at_end", None) is None:
        _local.at_end = _AtThreadEnd()
        _watched.add(get_native_id())


def register_current_thread(role: str) -> None:
    """Tag the calling thread with a role; call first thing in run()."""
    _roles[get_ident()] = _roles_by_nid[get_native_id()] = \
        _role_prefix + role
    _cpu_final.pop(get_native_id(), None)
    _watch_thread_end()


def unregister_current_thread() -> None:
    """The calling thread is about to end: its span records and its CPU
    time (it can still read its own clock) go to the retired sums of its
    role, so a window's difference does not lose it. A thread that took a
    role or opened a span does this by itself as it ends."""
    ident, nid = get_ident(), get_native_id()
    final = _cpu_final.get(nid)
    # said twice (the engine's loop, then the thread's end): the role is
    # the one it had the first time
    role = final[0] if final is not None else role_of(ident)
    st = _threads.pop(ident, None)
    if st is not None:
        _retire_spans(role, st)
    # its own last reading stands from here on (cpu_by_role reads it no
    # more, and folds it once the thread is gone)
    _cpu_final[nid] = (role, thread_time_ns())
    _roles.pop(ident, None)
    _roles_by_nid.pop(nid, None)
    _phases.pop(ident, None)


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

    __slots__ = ("stats", "child_ns", "flat", "waits")

    def __init__(self):
        # name -> [count, total_ns, self_ns, long_n, long_self_ns]; self is
        # total minus the spans closed inside it, so self times partition
        # the thread's time; long_* count the closes whose self time
        # reached LONG_SELF_NS
        self.stats: Dict[str, List[int]] = {}
        self.child_ns = 0   # time of the spans closed so far in the open one
        # the span set_phase holds open at this nesting level:
        # (name, annotation, t0, child_ns beneath it, marker beneath it)
        self.flat = None
        # name -> cpu_ns: the thread's CPU time between the two ends of its
        # wait_spans; a name is here if and only if the span waits by design
        self.waits: Dict[str, int] = {}


_threads: Dict[int, _ThreadSpans] = {}
_annotation_names: Dict[str, str] = {}   # "rpc.parse" -> "brpc.rpc.parse"
_trace_annotation = None   # jax.profiler.TraceAnnotation, once jax is there


def _spans_of(ident: int) -> _ThreadSpans:
    st = _threads.get(ident)
    if st is None:
        st = _threads[ident] = _ThreadSpans()
        if not _gc_hooked:
            _hook_gc()
        _watch_thread_end()
        _cpu_final.pop(get_native_id(), None)   # it unregistered, lives on
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


def _close(st: _ThreadSpans, t1: int, name: str, ann, t0: int,
           outer_child: int) -> int:
    """Leave a span at ``t1``: annotation, counters, the parent's child
    time. The caller read the clock and may hand the same reading to the
    span it opens next."""
    dt = t1 - t0
    if ann is not None:
        ann.__exit__(None, None, None)
    rec = st.stats.get(name)
    if rec is None:
        rec = st.stats[name] = [0, 0, 0, 0, 0]
    own = dt - st.child_ns
    rec[0] += 1
    rec[1] += dt
    rec[2] += own
    if own >= LONG_SELF_NS:
        rec[3] += 1
        rec[4] += own
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
        t1 = perf_counter_ns()
        if st.flat is not None:   # a set_phase left open inside this span
            _close(st, t1, *st.flat[:4])
        outer_child, st.flat = self._outer
        self.elapsed_ns = _close(st, t1, self.name, self._ann, self._t0,
                                 outer_child)
        if self._prev is None:
            _phases.pop(get_ident(), None)
        else:
            _phases[get_ident()] = self._prev
        return False


class wait_span(span):
    """A span that waits for something BY DESIGN (nothing to run, a full
    pool, the device). It reads the thread's CPU clock just inside its two
    ends (the reads' own time is the wait's, not its neighbours') and adds
    the difference to ``thread_waits()[name]``: what a wait that should
    sleep spent on the CPU, and what to take off the thread's CPU time to
    get that of its working spans."""

    __slots__ = ("_c0",)

    def __enter__(self):
        span.__enter__(self)
        self._c0 = thread_time_ns()
        return self

    def __exit__(self, *exc):
        waits = self._st.waits
        waits[self.name] = (waits.get(self.name, 0)
                            + thread_time_ns() - self._c0)
        return span.__exit__(self, *exc)


def set_phase(name: Optional[str], **ids) -> Optional[str]:
    """The dispatch fast paths' form of :class:`span`: leave the span this
    thread's last ``set_phase`` opened and enter ``name`` in its place, so
    consecutive phases lie side by side, never inside one another. Returns
    the previous marker; handing that back (or None) restores it without
    opening a span — the enclosing :class:`span`, if any, is still open.
    Where one span closes and the next opens, ONE read of the clock serves
    both."""
    ident = get_ident()
    prev = _phases.get(ident)
    st = _spans_of(ident)
    flat = st.flat
    base = flat[4] if flat is not None else prev
    opens = name is not None and name != base
    if flat is not None or opens:
        t = perf_counter_ns()
        if flat is not None:
            st.flat = None
            _close(st, t, *flat[:4])
    if name is None:
        if prev is not None:
            del _phases[ident]
    else:
        _phases[ident] = name
        if opens:
            outer_child, st.child_ns = st.child_ns, 0
            st.flat = (name, _annotate(name, ids), t, outer_child, base)
    return prev


def phase_of(ident: int) -> Optional[str]:
    """The sampler's view of a thread's phase: the innermost open span's
    name without its layer (``rpc.parse`` and a bare ``parse`` both read
    ``parse``)."""
    name = _phases.get(ident)
    return name.rpartition(".")[2] if name else name


def thread_spans() -> Dict[str, List[int]]:
    """The calling thread's span counters, ``{name: [count, total_ns,
    self_ns, long_n, long_self_ns]}`` — the live dict, so the thread's
    owner can keep reading it (``ServingEngine.snapshot``) after the
    thread has gone."""
    return _spans_of(get_ident()).stats


def thread_waits() -> Dict[str, int]:
    """The calling thread's spans that wait by design, ``{name: cpu_ns}``
    with the CPU time used inside them — the live dict, as
    :func:`thread_spans` is."""
    return _spans_of(get_ident()).waits


# ------------------------------------------- the whole process, by role
# role -> {name: record}: the span records of threads that have ended
_retired_spans: Dict[str, Dict[str, List[int]]] = {}
# role -> [threads, cpu_ns]: the CPU time of threads that have ended
_retired_cpu: Dict[str, List[int]] = {}
# native id -> (role, cpu_ns): each thread's last CPU reading, as
# cpu_by_role took it (_cpu_seen, written under _cpu_lock only) and as the
# thread itself took it when it unregistered (_cpu_final, written by that
# thread only; it stands over the other). Keyed by the kernel's thread id,
# which (unlike ``ident``) is not handed to the next thread at once.
_cpu_seen: Dict[int, tuple] = {}
_cpu_final: Dict[int, tuple] = {}
_cpu_lock = threading.Lock()     # cpu_by_role's fold of the two above

_gc_hooked = False
_gc = [0, 0, 0]      # collections, pause_ns, max_ns
_gc_t0 = 0


def _add_records(into: Dict[str, List[int]], st: _ThreadSpans) -> None:
    """Add one thread's span records to a role's (the thread may be
    writing them: ``list(items())`` is one step of the interpreter)."""
    for name, rec in list(st.stats.items()):
        have = into.get(name)
        if have is None:
            into[name] = list(rec)
        else:
            for i, v in enumerate(rec):
                have[i] += v


def _retire_spans(role: str, st: _ThreadSpans) -> None:
    _add_records(_retired_spans.setdefault(role, {}), st)


def spans_by_role() -> Dict[str, Dict[str, List[int]]]:
    """Every thread's span records, live and retired, summed by role:
    ``{role: {name: [count, total_ns, self_ns, long_n, long_self_ns]}}``.
    Cumulative, so two calls difference."""
    out = {role: {name: list(rec) for name, rec in spans.items()}
           for role, spans in list(_retired_spans.items())}
    for ident, st in list(_threads.items()):
        _add_records(out.setdefault(role_of(ident), {}), st)
    return out


def _thread_cpu_ns(native_id: int) -> int:
    """Another thread's CPU clock, user + system. The clock's id is made
    from the kernel's thread id as ``pthread_getcpuclockid`` makes it
    (``MAKE_THREAD_CPUCLOCK(tid, CPUCLOCK_SCHED)``), without touching the
    thread's ``pthread_t``, which is stale memory once the thread has
    ended; for an ended thread the kernel answers EINVAL (``OSError``)."""
    return clock_gettime_ns((~native_id << 3) | 6)


def cpu_by_role(unlisted: Optional[Dict[str, List[int]]] = None
                ) -> Dict[str, List[int]]:
    """``{role: [threads, cpu_ns]}``: the CPU clock of every live Python
    thread summed by the role it registered (``user``: none), with the
    retired sums of threads that ended, ``unlisted`` (the caller's count of
    threads no ``threading.Thread`` stands for, in the same form: the
    native lane's ``lane.*``), ``process`` (``process_time_ns``) and
    ``runtime``, what the process holds beyond all of those (PJRT's, the
    profiler's). ``threads`` counts the live ones. Read at snapshot time
    only: one system call a thread."""
    out: Dict[str, List[int]] = {}
    with _cpu_lock:     # two snapshots at once must not retire one twice
        # known before the listing: a thread born after it is not taken
        # for dead
        known = set(_cpu_seen).union(list(_cpu_final), list(_roles_by_nid))
        live = set()
        for th in threading.enumerate():
            nid = th.native_id
            if nid is None:
                continue
            live.add(nid)
            if nid in _cpu_final:
                continue
            try:
                cpu = _thread_cpu_ns(nid)
            except OSError:    # ended between the listing and the read
                continue
            role = _roles_by_nid.get(nid)
            _cpu_seen[nid] = (role if role is not None
                              else _role_prefix + ROLE_USER, cpu)
        for nid in known.union(live):
            last = _cpu_final.get(nid) or _cpu_seen.get(nid)
            if nid in live or nid in _watched:
                # (watched and not live: it has left the listing and has
                # yet to take its own last reading; it counts as no thread)
                into, threads = out, nid in live
            else:    # gone since its last reading, which is what is kept
                _roles_by_nid.pop(nid, None)
                _cpu_seen.pop(nid, None)
                _cpu_final.pop(nid, None)
                into, threads = _retired_cpu, 1
            if last is not None:
                mine = into.setdefault(last[0], [0, 0])
                mine[0] += threads
                mine[1] += last[1]
        for role, (_n, cpu) in _retired_cpu.items():
            out.setdefault(role, [0, 0])[1] += cpu
    for role, (n, cpu) in (unlisted or {}).items():
        mine = out.setdefault(role, [0, 0])
        mine[0] += n
        mine[1] += cpu
    process = process_time_ns()
    listed = sum(cpu for _n, cpu in out.values())
    out["runtime"] = [0, max(0, process - listed)]
    out["process"] = [len(live), process]
    return out


def _on_gc(phase: str, info: dict) -> None:
    global _gc_t0
    if phase == "start":
        _gc_t0 = perf_counter_ns()
    elif _gc_t0:
        dt = perf_counter_ns() - _gc_t0
        _gc_t0 = 0
        _gc[0] += 1
        _gc[1] += dt
        if dt > _gc[2]:
            _gc[2] = dt


def _hook_gc() -> None:
    global _gc_hooked
    _gc_hooked = True
    gc.callbacks.append(_on_gc)


def gc_pauses() -> List[int]:
    """``[collections, pause_ns, max_ns]`` of the cyclic collector since
    the registry's first span (one ``gc.callbacks`` hook; a collection
    stops the thread that triggered it, with the interpreter held)."""
    return list(_gc)


# ----------------------------------------------------------------- hygiene
def prune(live_idents) -> None:
    """Drop registry entries for dead thread idents (idents are reused by
    the OS; the sampler calls this with sys._current_frames() keys, which
    cover every live thread). A dead thread's span records go to its
    role's retired sums."""
    live = set(live_idents)
    for ident in [i for i in _threads if i not in live]:
        st = _threads.pop(ident, None)
        if st is not None:
            _retire_spans(role_of(ident), st)
    for d in (_roles, _phases):
        for ident in [i for i in d if i not in live]:
            d.pop(ident, None)


def reset_for_test() -> None:
    global _role_prefix, _gc_hooked, _gc_t0
    _roles.clear()
    _roles_by_nid.clear()
    _phases.clear()
    _threads.clear()
    _retired_spans.clear()
    _retired_cpu.clear()
    _cpu_seen.clear()
    _cpu_final.clear()
    _watched.clear()
    _role_prefix = ""
    if _gc_hooked:
        gc.callbacks.remove(_on_gc)
    _gc_hooked = False
    _gc_t0 = 0
    _gc[:] = [0, 0, 0]
