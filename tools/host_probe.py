"""The host counters of one benchmark run, UNTRACED as well as traced.

The harness prints per-layer metrics in ``--trace 1`` runs only, and there
the profiler is part of what the host counters count (PERF.md section 3).
This tool runs one cell through ``benchmark/run.py`` as it stands, keeps the
two ``engine.snapshot()`` of the window (it wraps
``harness.serve_runner.measure``; nothing of the benchmark is edited), and
prints, after the run's own line, ONE line ``HOSTPROBE {...}`` with:

- ``metrics``: the run's end-to-end metrics (and per-layer ones, if traced);
- ``host``: the six counters of ``harness/host_counters.py``, by its readers;
- ``loop_ms_a_step``: the loop thread's spans, ``[self wall ms a step,
  long_n, long self ms]``, and ``waits_cpu_ms_a_step``, the CPU inside the
  spans that wait by design;
- ``loop_self_over_wall``: the loop's self times over ``wall_us`` (every
  instant of the loop lies in a span: 0.995 or more);
- ``threads_cpu_s``: CPU seconds by thread role in the window;
- ``lane_wait``, ``gc``: the window's differences.

With ``--out DIR`` the two ``host`` groups are written to ``DIR/<tag>.json``.
Run it from the root of the checkout to measure, which may be another than
this file's (a copy of the parent commit, which has no ``host``: the line
then carries the end-to-end metrics alone). On the chip: ``chiprun -- python
tools/host_probe.py ...``. Every option but the tool's own goes to
``benchmark/run.py``:

    python tools/host_probe.py [--tag NAME] [--out DIR] \\
        --workload chat-steady --seed 3700000501 --seconds 30 --trace 0

Six processes on six seeds give the counters' spread, which no traced run
can: ``statistics.quantiles`` over the six ``host`` groups.
"""

import argparse
import contextlib
import importlib.util
import io
import json
import os
import sys
import types

COUNTERS = ("loop_cpu_ms", "loop_offcpu_ms", "loop_stall_ms",
            "contender_cpu_share", "client_cpu_share", "lane_wait_ms")


class _Tee(io.TextIOBase):
    """The run's output, passed on and kept (its last line is the result)."""

    def __init__(self):
        self.kept = io.StringIO()

    def write(self, s):
        self.kept.write(s)
        sys.__stdout__.write(s)
        return len(s)

    def flush(self):
        sys.__stdout__.flush()


def _difference(h0, h1, steps):
    """What the window's two ``host`` groups say beside the six counters."""
    wall = h1["wall_us"] - h0["wall_us"]
    loop = {name: [b - a for a, b in
                   zip(h0["loop"].get(name, [0, 0, 0]), rec)]
            for name, rec in h1["loop"].items()}
    per = 1e3 * max(1, steps)
    return {
        "wall_s": wall / 1e6,
        "loop_self_over_wall": sum(r[0] for r in loop.values()) / wall,
        "loop_ms_a_step": {name: [round(r[0] / per, 4), r[1],
                                  round(r[2] / 1e3, 1)]
                           for name, r in sorted(loop.items())},
        "waits_cpu_ms_a_step": {
            name: round((cpu - h0["waits"].get(name, 0)) / per, 4)
            for name, cpu in sorted(h1["waits"].items())},
        "threads_cpu_s": {
            role: round((rec[1] - h0["threads"].get(role, [0, 0])[1]) / 1e6,
                        4) for role, rec in h1["threads"].items()},
        "lane_wait": {kind: [rec[0] - h0["lane_wait"][kind][0],
                             round(rec[1] - h0["lane_wait"][kind][1], 1),
                             rec[2]]
                      for kind, rec in h1["lane_wait"].items()},
        "gc": [b - a for a, b in zip(h0["gc"], h1["gc"])][:2]
        + [h1["gc"][2]],
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tag", default="probe")
    ap.add_argument("--out", default=None,
                    help="directory for <tag>.json (the two host groups)")
    args, run_argv = ap.parse_known_args(argv)

    root = os.getcwd()      # the checkout to run: this tool's, or another's
    bench = os.path.join(root, "benchmark")
    sys.path.insert(0, bench)
    sys.path.insert(1, root)
    spec = importlib.util.spec_from_file_location(
        "bench_run", os.path.join(bench, "run.py"))
    bench_run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench_run)
    from harness import serve_runner

    kept = {}
    measure = serve_runner.measure

    def keeping(*a, **kw):
        kept["window"] = measure(*a, **kw)
        return kept["window"]

    serve_runner.measure = keeping
    tee = _Tee()
    with contextlib.redirect_stdout(tee):
        rc = bench_run.main(run_argv)
    lines = [ln for ln in tee.kept.getvalue().splitlines() if "{" in ln]
    result = json.loads(lines[-1][lines[-1].index("{"):]) if lines else {}
    probe = {"tag": args.tag, "rc": rc, "argv": run_argv,
             "correct": result.get("correct"),
             "failed": result.get("failed"),
             "compiles_in_window": result.get("compiles_in_window"),
             "metrics": {k: v["value"] for k, v
                         in (result.get("metrics") or {}).items()}}
    win = kept.get("window") or {}
    a, b = win.get("snap0") or {}, win.get("snap1") or {}
    hosts = {}
    if "host" in a and "host" in b:      # an older program has none
        try:
            from harness import host_counters
            run = types.SimpleNamespace(window=win)
            probe["host"] = {name: getattr(host_counters, name)(run)
                             for name in COUNTERS}
        except ImportError:              # an older benchmark has no readers
            pass
        probe["steps"] = b["steps"] - a["steps"]
        probe.update(_difference(a["host"], b["host"], probe["steps"]))
        hosts = {"host0": a["host"], "host1": b["host"],
                 "steps": [a["steps"], b["steps"]]}
    if args.out:
        os.makedirs(args.out, exist_ok=True)
        with open(os.path.join(args.out, args.tag + ".json"), "w") as f:
            json.dump(dict(hosts, probe=probe), f)
    print("HOSTPROBE " + json.dumps(probe), flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
