"""Find the knee of an open-loop serving cell, once: one process, one
set-up, the cell's mix offered at each of a few fixed rates in turn.

    python3 benchmark/sweep.py --workload chat-steady --rates 2,2.5,3,3.5,4,5 --seconds 25
    python3 benchmark/sweep.py --workload chat-steady --rates 2,2.5 --orders 1,2,3,4 --seconds 30

For each rate: requests sent, finished and failed, the tails, the tokens per
second completed, how long the queue took to drain after the window closed,
and the mean time to first token of the last third of the requests over that
of the first third (a backlog that grows all through the run reads well over
1). The knee is the highest rate at which nothing failed, the drain is short
and that ratio stays near 1; the cell's rate is four fifths of it. With
``--orders`` every rate is offered in each of those orders of the same
arrivals and sizes (the mix's ``order_seed``): how far the order alone moves
the cell at that rate. A benchmark PR runs this again when an optimisation
has moved the knee.
"""

import time

T0 = time.monotonic()

import argparse   # noqa: E402
import json       # noqa: E402
import os         # noqa: E402
import sys        # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)
sys.path.insert(1, ROOT)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--orders", default="")
    ap.add_argument("--seconds", type=float, default=25.0)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--rehearse-cpu", nargs="?", type=int, const=1, default=0)
    args = ap.parse_args()
    args.trace = 0

    import numpy as np

    from harness import common, loadgen, manifest, serve_runner as sr

    man = manifest.load(ROOT)
    cell = manifest.cell_of(man, args.workload)
    _cache, ok = common.start_jax(args.rehearse_cpu, cell["chips"])
    if not ok:
        print("sweep: needs a TPU", file=sys.stderr)
        return 3
    run = common.Run(T0, args, cell, manifest.config_of(man, ROOT, cell),
                     manifest.load_json(ROOT, manifest.traffic_path(man, cell)),
                     [], [])
    runner_args = run.size(run.cfg["runner_args"])
    vocab = runner_args["model"]["vocab"]
    served = sr.Served(runner_args, args.seed, run.say)
    rates = [float(r) for r in args.rates.split(",")]
    mix = run.size(run.mix)
    sr.warm_up(served, loadgen.build_schedule(
        dict(mix, rate_per_s=max(rates)), args.seed, args.seconds, vocab),
        run.say)
    orders = [int(o) for o in args.orders.split(",") if o] or [None]
    for i, (rate, order) in enumerate((r, o) for r in rates for o in orders):
        m = dict(mix, rate_per_s=rate)
        if order is not None:
            m["order_seed"] = order
        reqs = loadgen.build_schedule(m, args.seed + i, args.seconds, vocab)
        if served.engine.prefix is not None:
            served.engine.prefix.clear()
        c0 = run.compiles.n
        win = sr.measure(served, m, reqs, args.seconds)
        sent = win["sent"]
        ttft = loadgen.ttft_ms(sent)
        third = max(1, len(sent) // 3)
        done_t = max((r.t_done for r in sent if r.t_done), default=0.0)
        toks = sum(len(r.tokens) for r in sent if r.finished)
        snap0, snap1 = win["snap0"], win["snap1"]
        steps = max(1, snap1["steps"] - snap0["steps"])
        rec = {"rate_per_s": rate, "order_seed": m.get("order_seed"),
               "sent": len(sent),
               "finished": sum(r.finished for r in sent),
               "failed": sum(not r.finished for r in sent),
               "ttft_p50_ms": loadgen.percentile(ttft, 50),
               "ttft_p90_ms": loadgen.percentile(ttft, 90),
               "ttft_last_over_first_third":
                   float(np.mean(ttft[-third:]) / np.mean(ttft[:third])),
               "drain_s": max(0.0, done_t - win["t_close"]),
               "out_tok_s": toks / (max(done_t, win["t_close"]) - win["t0"]),
               "tokens_per_step":
                   (snap1["tokens_generated"] - snap0["tokens_generated"])
                   / steps,
               "kv_peak_share": win["kv_peak_share"],
               "late_max_ms": win["late_max_ms"],
               "compiles_in_window": run.compiles.n - c0,
               **sr.also(sent), **sr.end_to_end(sent)}
        print(json.dumps(rec), flush=True)
    served.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())
