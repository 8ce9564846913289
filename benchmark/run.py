"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>
    python3 benchmark/run.py --check                     # manifest and files agree
    python3 benchmark/run.py --workload <cell> --rehearse-cpu [N]   # tiny shapes, CPU

One run is one process: it finds the cell in ``BENCHMARK.json``, the cell's
configuration, traffic and per-layer readers by their names, stands the
system up, warms this cell's shapes (all of that is ``setup_s``), measures
for ``--seconds``, decides ``correct`` against the plain reference, and
prints ONE JSON object as the last line of standard output. Each number
compared is printed beside its limit, last on standard error and last in
the line. Without a TPU (or with fewer chips than the cell asks for) it
exits non-zero and prints no result; ``--rehearse-cpu`` runs the same code
at the tiny shapes of the files' ``rehearsal`` groups for debugging, labels
every line and prints no result line.
"""

import time

T0 = time.monotonic()

import argparse   # noqa: E402
import importlib  # noqa: E402
import json       # noqa: E402
import os         # noqa: E402
import sys        # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)
sys.path.insert(1, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, default=0, choices=(0, 1))
    ap.add_argument("--check", action="store_true")
    ap.add_argument("--rehearse-cpu", nargs="?", type=int, const=1, default=0,
                    metavar="N", help="tiny shapes on N virtual CPU devices; "
                                      "never a chip result")
    args = ap.parse_args(argv)

    from harness import manifest

    man = manifest.load(ROOT)
    if args.check:
        bad = manifest.check(man, ROOT)
        for b in bad:
            print("check: " + b, file=sys.stderr)
        print(f"check: {len(man['workloads'])} cells, "
              f"{len(man['end_to_end'])} end-to-end and "
              f"{len(man['per_layer'])} per-layer metrics: "
              + ("OK" if not bad else f"{len(bad)} faults"))
        return 1 if bad else 0
    if not args.workload:
        ap.error("--workload is required")
    cell = manifest.cell_of(man, args.workload)
    cfg = manifest.config_of(man, ROOT, cell)
    mix = manifest.load_json(ROOT, manifest.traffic_path(man, cell))
    if args.seconds is None:
        args.seconds = float(man["run_seconds"])
    from harness import common, peaks

    cache_dir, ok = common.start_jax(args.rehearse_cpu, cell["chips"])
    import jax

    devs = jax.devices()
    if not ok:
        print(f"benchmark: cell {cell['name']!r} needs {cell['chips']} TPU "
              f"chip(s), but JAX reports {len(devs)} x "
              f"{devs[0].platform!r} ({devs[0].device_kind}). "
              f"--rehearse-cpu is for debugging and gives no result.",
              file=sys.stderr)
        return 3
    run = common.Run(T0, args, cell, cfg, mix,
                     manifest.metrics_of(man, cell, "end_to_end"),
                     manifest.metrics_of(man, cell, "per_layer"))
    if not args.rehearse_cpu:
        run.peak = peaks.chip_peak(devs[0].device_kind)
    run.say(f"cell {cell['name']}: config {cell['config']} "
            f"(runner {cfg['runner']}), traffic {cell['traffic']}, seed "
            f"{args.seed}, {args.seconds}s, trace {args.trace}; "
            f"{len(devs)} x {devs[0].device_kind}; jax {jax.__version__}; "
            f"compile cache {cache_dir}")
    runner = importlib.import_module(f"harness.{cfg['runner']}_runner")
    result = runner.run(run)
    for name, c in result["checks"].items():   # the line's last key
        print(f"{run.tag}compared {name} = {c['value']!r} "
              f"(limit {c['limit']!r})", file=sys.stderr, flush=True)
    line = json.dumps(result)
    if args.rehearse_cpu:
        print(run.tag + "no result: a rehearsal on the CPU measures "
              "nothing. Its line, for debugging: " + line, flush=True)
    else:
        print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
