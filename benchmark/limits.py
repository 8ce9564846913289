"""The readings a limit is set from, taken on the chip at the cells' own size.

    python3 benchmark/limits.py --workloads prefill-closed,chat-steady \
        --seeds 11,12,13,... --control-seeds 11,12,13 --seconds 8
    python3 benchmark/limits.py --workloads train-16k-sp4 --seeds 21,22,23 \
        --control-seeds 21,22,23 --faults no_exchange,half_tokens

One process reads many seeds (set-up is long): for every seed it stands the
cell's system up as ``run.py`` does, drives it through a short window (serve)
or its first steps (train), and compares with the reference -- the LOWER
reading of each number. For a control seed it also puts the reference
computed in the nearest lower precision in the program's place -- the UPPER
reading -- and, for training, the reference with a fault planted. Cells that
share a configuration share one stood-up system per seed. One JSON line per
seed and cell goes to standard output and to ``chiprun_out/limits.jsonl``;
``PERF.md`` records the readings and the limits set from them. The
benchmark's own runs never run this.
"""

import time

T0 = time.monotonic()

import argparse   # noqa: E402
import json       # noqa: E402
import os         # noqa: E402
import sys        # noqa: E402

import numpy as np   # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)
sys.path.insert(1, ROOT)


def emit(run, rec: dict) -> None:
    line = json.dumps(rec)
    print(run.tag + line, flush=True)
    if run.rehearsal:      # a walk through the code on the CPU: no record
        return
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "limits.jsonl"), "a") as f:
        f.write(line + "\n")


def serve_seed(runs, seed, seconds, control, modes):
    """All serve cells of one configuration, on one stood-up system. With
    ``control`` each cell's verdict is taken twice: of the program, and of
    the control put in its place (``control_correct`` has to read false).
    ``modes``: further arithmetics of the reference to read the same
    windows against, beside the configuration's own."""
    from harness import common, loadgen, reference, serve_runner as sr

    first = runs[0]
    runner_args = first.size(first.cfg["runner_args"])
    model_args = runner_args["model"]
    ref_weights = reference.HostWeights(seed % 2**32, model_args)
    served = sr.Served(runner_args, seed, first.say)
    windows = []
    for run in runs:
        mix = run.size(run.mix)
        reqs = loadgen.build_schedule(mix, seed, seconds,
                                      model_args["vocab"])
        if served.engine.prefix is not None:
            served.engine.prefix.clear()   # the last cell's cached chains
        sr.warm_up(served, reqs, run.say)
        c0 = run.compiles.n
        win = sr.measure(served, mix, reqs, seconds)
        held = sr.held_rows(served, win["sent"],
                            int(mix["check_kv_requests"]), seed,
                            int(mix.get("check_pad", 512)))
        # to the host: two cells' rows beside the program fill the chip
        held = {k: (n, tuple(np.asarray(a) for a in kv))
                for k, (n, kv) in held.items()}
        windows.append((run, mix, win, held, run.compiles.n - c0))
    served.stop()
    del served
    ref = reference.ServeReference(
        seed % 2**32, model_args, runner_args["reference"]["mode"],
        host_weights=ref_weights.get(),
        pad_to=int(windows[0][1].get("check_pad", 512)))
    for mode in [ref.mode] + [m for m in modes if m != ref.mode]:
        ref.mode = mode
        for run, mix, win, held, compiles in windows:
            sent = win["sent"]
            sample = sr.sample_of(sent, held, int(mix["check_requests"]),
                                  seed)
            v = sr.judge(sent, sample, held, ref, mix, run.mix["limits"])
            rec = {"cell": run.cell["name"], "seed": seed,
                   "seconds": seconds, "sent": len(sent),
                   "compiles_in_window": compiles, "mode": mode,
                   "correct": common.correct_of(v["checks"]),
                   **{k: c["value"] for k, c in v["checks"].items()},
                   **{k: v[k] for k in ("sample_requests", "sample_tokens",
                                        "sample_tokens_not_ref_best",
                                        "kv_requests", "kv_rows",
                                        "kv_gap_by_layer")},
                   **sr.end_to_end(sent)}
            if control:
                c = sr.judge(sent, sample, held, ref, mix,
                             run.mix["limits"], control=True)
                rec["control_correct"] = common.correct_of(c["checks"])
                rec["control"] = {k: x["value"]
                                  for k, x in c["checks"].items()}
                rec["control_tokens_not_ref_best"] = \
                    c["sample_tokens_not_ref_best"]
                rec["control_kv_gap_by_layer"] = c["kv_gap_by_layer"]
            emit(run, rec)
    ref.free()


def train_seeds(run, seeds, controls, faults):
    """The program's first steps seed by seed on one compiled step, then
    every reference, control and fault: one job a device, all at once."""
    from harness import reference, train_runner as tr

    args, mix = run.size(run.cfg["runner_args"]), run.size(run.mix)
    steps = int(mix["check_steps"])
    trainer, got = None, {}
    for seed in seeds:
        if trainer is None:
            trainer = tr.Trainer(run, args, mix, seed)
        else:
            trainer.reset(seed)
        got[seed] = tr.first_steps(trainer, steps)
        run.say(f"program seed {seed}: losses {got[seed]['losses']}")
    trainer.free()
    del trainer
    jobs = [{"seed": s} for s in seeds]
    for s in seeds:
        if s in controls:
            jobs.append({"seed": s, "lower": True, "kind": "control"})
            jobs += [{"seed": s, "fault": f, "kind": "fault." + f}
                     for f in faults]
    t = time.monotonic()
    outs = reference.train_reference_jobs(
        jobs, args["model"], int(mix["batch"]), int(mix["seq"]),
        float(args["lr"]), steps)
    run.say(f"{len(jobs)} reference jobs in {time.monotonic() - t:.1f}s")
    refs = {j["seed"]: o for j, o in zip(jobs, outs) if "kind" not in j}

    def values(checks):
        return {k: c["value"] for k, c in checks.items()}

    for seed in seeds:
        rec = {"cell": run.cell["name"], "seed": seed,
               "program": values(tr.judge(got[seed], refs[seed],
                                          run.mix["limits"])),
               "losses": got[seed]["losses"],
               "ref_losses": refs[seed]["losses"]}
        for j, o in zip(jobs, outs):
            if j["seed"] == seed and "kind" in j:
                rec[j["kind"]] = values(tr.judge(o, refs[seed],
                                                 run.mix["limits"]))
        emit(run, rec)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workloads", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--faults", default="")
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--modes", default="", help="further arithmetics of "
                    "the serving reference to read against")
    ap.add_argument("--rehearse-cpu", nargs="?", type=int, const=1, default=0)
    args = ap.parse_args()
    args.trace, args.seed = 0, 0

    from harness import common, manifest

    man = manifest.load(ROOT)
    cells = [manifest.cell_of(man, w) for w in args.workloads.split(",")]
    _cache, ok = common.start_jax(args.rehearse_cpu,
                                  max(c["chips"] for c in cells))
    if not ok:
        print("limits: needs the cells' TPU chips", file=sys.stderr)
        return 3
    runs = []
    for cell in cells:
        cfg = manifest.config_of(man, ROOT, cell)
        mix = manifest.load_json(ROOT, manifest.traffic_path(man, cell))
        runs.append(common.Run(T0, args, cell, cfg, mix, [], []))
    seeds = [int(s) for s in args.seeds.split(",")]
    controls = {int(s) for s in args.control_seeds.split(",") if s}
    faults = [f for f in args.faults.split(",") if f]
    if runs[0].cfg["runner"] == "train":
        train_seeds(runs[0], seeds, controls, faults)
        return 0
    for seed in seeds:
        serve_seed(runs, seed, args.seconds, seed in controls,
                   [m for m in args.modes.split(",") if m])
    return 0


if __name__ == "__main__":
    sys.exit(main())
