"""The host side of a step on the CPU clock: six per-layer metrics read from
``engine.snapshot()["host"]`` on both sides of the window (``run.window``'s
``snap0`` and ``snap1``), as ``queue_wait_ms.*`` and ``batch_occupancy``
are. Counters, not the trace: they read in an untraced run too
(``tools/host_probe.py`` prints them there), so their spread over processes
can be taken.

``host`` holds, cumulative since the engine started (docs/serving.md
"Reading a profile"): ``wall_us``; ``loop``, the loop thread's spans as
``[self_us, long_n, long_self_us]``; ``waits``, those of them that wait for
something by design (nothing to run, a full pool, the device) with the CPU
time used inside them; ``spans``, every thread's by role as ``[count,
self_us]``; ``threads``, ``[threads, cpu_us]`` by role with ``process`` and
``runtime``; ``lane_wait``, ``[events, wait_us, max_us]`` by kind; ``gc``.
Every other span of the loop is WORKING: it waits for nothing, so its time
off the CPU is a wait for the interpreter, for a blocking write or for a
core. The program says which spans wait; this module keeps no list. A
program without ``host`` (an older one) gives every reader None.

What the harness prints is the TRACED run's reading (per-layer metrics print
with ``--trace 1`` only). Under the profiler every span also enters an
annotation, which about doubles ``loop_cpu_ms``, and ``snap1`` is taken
after the profiler has stopped and written its file, so ``wall_us`` and the
CPU of the three rates (``loop_stall_ms``, ``contender_cpu_share``,
``client_cpu_share``) cover that stop too (PERF.md section 3 has the sizes).
"""


def window(run):
    """``(host0, host1, steps, wall_us)`` of the window: the two ``host``
    groups and the differences of ``steps`` and ``wall_us``; None without
    ``host``."""
    a, b = run.window.get("snap0"), run.window.get("snap1")
    if not a or not b or "host" not in a or "host" not in b:
        return None
    h0, h1 = a["host"], b["host"]
    return h0, h1, b["steps"] - a["steps"], h1["wall_us"] - h0["wall_us"]


def _working(h0, h1, column):
    """The window's difference of one column of ``loop``, summed over the
    working spans."""
    return sum(rec[column] - h0["loop"].get(name, (0, 0, 0))[column]
               for name, rec in h1["loop"].items() if name not in h1["waits"])


def _cpu(h0, h1, role):
    return (h1["threads"].get(role, (0, 0))[1]
            - h0["threads"].get(role, (0, 0))[1])


def _working_cpu(h0, h1):
    """The loop thread's CPU time less what it used inside its waiting
    spans: the CPU its working spans cost (with what little of the thread's
    time lies in no span)."""
    return _cpu(h0, h1, "serving") - sum(
        cpu - h0["waits"].get(name, 0) for name, cpu in h1["waits"].items())


def loop_cpu_ms(run):
    w = window(run)
    if w is None or not w[2]:
        return None
    h0, h1, steps, _wall = w
    return _working_cpu(h0, h1) / steps / 1e3


def loop_offcpu_ms(run):
    w = window(run)
    if w is None or not w[2]:
        return None
    h0, h1, steps, _wall = w
    return (_working(h0, h1, 0) - _working_cpu(h0, h1)) / steps / 1e3


def loop_stall_ms(run):
    w = window(run)
    if w is None or not w[3]:
        return None
    h0, h1, _steps, wall = w
    return 1e3 * _working(h0, h1, 2) / wall


def contender_cpu_share(run):
    w = window(run)
    if w is None or not w[3]:
        return None
    h0, h1, _steps, wall = w
    return 100.0 * (_cpu(h0, h1, "process") - _cpu(h0, h1, "serving")) / wall


def client_cpu_share(run):
    w = window(run)
    if w is None or not w[3]:
        return None
    h0, h1, _steps, wall = w
    return 100.0 * _cpu(h0, h1, "user") / wall


def lane_wait_ms(run):
    w = window(run)
    if w is None:
        return None
    n0, wait0, _max0 = w[0]["lane_wait"]["request"]
    n1, wait1, _max1 = w[1]["lane_wait"]["request"]
    if n1 == n0:
        return None
    return (wait1 - wait0) / (n1 - n0) / 1e3
