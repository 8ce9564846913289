"""Runner ``train``: ``tpu/train.py``'s jitted step
(``make_train_step``) on a dp x sp x tp mesh, driven from the seed.

Set-up builds ONE object -- the compiled step with its parameters -- drives
it through its first steps by the window's own call and feed, keeps the
readings the reference will be held against (each step's loss, per leaf the
norm of the first update over lr and of the change after the steps), and
hands that same object to the window. Nothing is read from the program but
the step function, its shardings and ``init_params``.
"""

from __future__ import annotations

import time

from . import reference


def _dtype(name: str):
    import jax.numpy as jnp

    return {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[name]


class Trainer:
    def __init__(self, run, args: dict, mix: dict, seed: int):
        import jax

        from brpc_tpu.tpu import mesh as meshlib, train

        self.m = dict(args["model"])
        self.lr = float(args["lr"])
        self.batch, self.seq = int(mix["batch"]), int(mix["seq"])
        devices = jax.devices()[:run.cell["chips"]] if not run.rehearsal \
            else jax.devices()
        self.mesh = meshlib.make_mesh(dict(args["mesh"]), devices)
        m = dict(self.m)
        tcfg = train.ModelConfig(
            **{k: v for k, v in m.items() if k != "dtype"},
            dtype=_dtype(m["dtype"]))
        self.step, pshard, self.bshard = train.make_train_step(
            tcfg, self.mesh, lr=self.lr)
        # the weights: on the device, in one jitted call from the seed, in
        # the type they are trained in
        self._init = jax.jit(lambda key: train.init_params(key, tcfg),
                             out_shardings=pshard)
        self._feed = jax.jit(
            lambda seed, i: reference.train_batch(seed, i, self.batch,
                                                  self.seq, m["vocab"]),
            out_shardings=self.bshard)
        self.reset(seed)

    def reset(self, seed: int):
        """Fresh parameters and a fresh stream of batches from ``seed``."""
        import jax
        import numpy as np

        self.seed = np.uint32(seed % 2**32)
        self.params = self._init(jax.random.PRNGKey(seed % 2**32))
        self.n = 0          # steps sent

    def send(self):
        """The window's own call and feed: a fresh batch from the seed,
        one step. Returns the step's loss, not yet read."""
        self.params, loss = self.step(self.params,
                                      self._feed(self.seed, self.n))
        self.n += 1
        return loss

    def free(self):
        self.params = None


def first_steps(tr: Trainer, steps: int) -> dict:
    """Drive the step object through its first steps and take the readings
    of the program that the reference is held against."""
    import jax
    import jax.numpy as jnp

    p0 = jax.tree_util.tree_map(jnp.copy, tr.params)
    losses, grad_norms = [], None
    for i in range(steps):
        losses.append(float(tr.send()))
        if i == 0:
            grad_norms = reference.leaf_change_norms(p0, tr.params,
                                                     1.0 / tr.lr)
    change = reference.leaf_change_norms(p0, tr.params, 1.0)
    return {"losses": losses, "grad_norms": grad_norms,
            "change_norms": change}


def window(tr: Trainer, seconds: float) -> dict:
    """Steps sent back to back, the loss of step n read after step n+1 is
    sent; when the time is up nothing more is sent, what is in flight is
    waited for, and the rate is all completed steps over all that time."""
    import jax

    losses, prev = [], None
    t0 = time.monotonic()
    while time.monotonic() - t0 < seconds:
        cur = tr.send()
        if prev is not None:
            losses.append(float(prev))
        prev = cur
    losses.append(float(prev))
    jax.block_until_ready(tr.params)
    elapsed = time.monotonic() - t0
    return {"steps": len(losses), "elapsed_s": elapsed, "losses": losses}


def traced_steps(tr: Trainer, tracer, n: int) -> None:
    """n more steps under the profiler, each launch in a
    ``bench.train_step`` annotation, inside one ``bench.window``."""
    import jax

    jax.block_until_ready(tr.params)
    tracer.start()
    with jax.profiler.TraceAnnotation("bench.window"):
        loss = None
        for _ in range(n):
            with jax.profiler.TraceAnnotation("bench.train_step"):
                loss = tr.send()
        jax.block_until_ready((tr.params, loss))
    tracer.stop()


def judge(got: dict, ref: dict, limits: dict) -> dict:
    """Each number compared, beside its limit: each step's loss against the
    reference's (as a share of it), and the worst leaf's gap of norms for
    the first gradient and for the change after the steps."""
    checks = {}
    for i, (a, b) in enumerate(zip(got["losses"], ref["losses"])):
        name = f"loss_gap_step{i + 1}"
        checks[name] = {"value": abs(a - b) / abs(b), "limit": limits[name]}
    skip = limits.get("skip_leaves_below", 1e-3)
    g, gleaf = reference.worst_leaf_gap(got["grad_norms"], ref["grad_norms"],
                                        skip)
    live = {k: v for k, v in ref["change_norms"].items()
            if ref["grad_norms"][k] >= skip * _median(ref["grad_norms"])}
    c, cleaf = reference.worst_leaf_gap(
        {k: got["change_norms"][k] for k in live}, live)
    checks["grad_norm_gap"] = {"value": g, "limit": limits["grad_norm_gap"],
                               "leaf": gleaf}
    checks["change_norm_gap"] = {"value": c,
                                 "limit": limits["change_norm_gap"],
                                 "leaf": cleaf}
    return checks


def _median(d: dict) -> float:
    v = sorted(d.values())
    return v[len(v) // 2]


def run(run) -> dict:
    import jax

    from . import common

    args = run.size(run.cfg["runner_args"])
    mix = run.size(run.mix)
    tr = Trainer(run, args, mix, run.args.seed)
    run.say(f"train step built on mesh {dict(tr.mesh.shape)}: "
            f"{tr.m}, B={tr.batch} S={tr.seq} lr={tr.lr}")
    steps = int(mix["check_steps"])
    got = first_steps(tr, steps)
    setup_s = time.monotonic() - run.t0
    run.say(f"set-up done: first {steps} steps, losses "
            f"{[round(l, 5) for l in got['losses']]}; window of "
            f"{run.args.seconds}s opens")
    compiles0 = run.compiles.n
    win = window(tr, run.args.seconds)
    compiles = run.compiles.n - compiles0
    run.window = win
    tokens = win["steps"] * tr.batch * tr.seq
    metrics = {"setup_s": setup_s, "tok_s": tokens / win["elapsed_s"]}
    run.say(f"window closed: {win['steps']} steps in "
            f"{win['elapsed_s']:.3f}s, last loss {win['losses'][-1]:.5f}, "
            f"{compiles} programs compiled inside the window")
    tracer = None
    if run.args.trace:
        tracer = common.Tracer()
        run.traced_steps = int(mix.get("traced_steps", 5))
        traced_steps(tr, tracer, run.traced_steps)
        run.train = {"batch": tr.batch, "seq": tr.seq, "model": tr.m,
                     "chips": len(tr.mesh.devices.flat)}
    device = common.device_record(run.cell["chips"])
    finite = all(l == l and abs(l) != float("inf") for l in win["losses"])
    tr.free()
    del tr

    t = time.monotonic()
    ref = reference.train_reference_steps(
        run.args.seed, args["model"], int(mix["batch"]), int(mix["seq"]),
        float(args["lr"]), steps)
    run.say(f"reference: {steps} steps in {time.monotonic() - t:.1f}s, "
            f"losses {[round(l, 5) for l in ref['losses']]}")
    checks = judge(got, ref, run.mix["limits"])
    checks["loss_not_finite"] = {"value": 0 if finite else 1, "limit": 0}
    result = {"correct": common.correct_of(checks),
              "attempted": win["steps"], "failed": 0}
    path = common.fill_metrics(run, result, metrics, device, tracer)
    if path:
        red = run.reduced
        run.say(f"trace: {path} window {red.window_s:.3f}s busy "
                f"{red.busy_mean_s:.3f}s over {len(red.devices)} devices")
    result["device"] = device
    result["compiles_in_window"] = compiles
    result["checks"] = checks
    return result
