"""What both runners share: the clock since process start, the labelled
log, the device record, the count of compilations, the traced window and
the dispatch to the per-layer metric readers."""

from __future__ import annotations

import importlib.util
import os
import shutil
import sys
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
TRACE_DIR = os.path.join(ROOT, ".bench_trace")


class Run:
    """One run's context, handed to the runner and then to the readers."""

    def __init__(self, t0, args, cell, cfg, mix, end_to_end, per_layer):
        self.t0 = t0                   # time.monotonic() at process start
        self.args = args
        self.cell, self.cfg, self.mix = cell, cfg, mix
        self.end_to_end = end_to_end   # the cell's end-to-end metric entries
        self.per_layer = per_layer     # the cell's per-layer metric entries
        self.rehearsal = bool(args.rehearse_cpu)
        self.tag = "[REHEARSAL cpu] " if self.rehearsal else ""
        self.peak = None               # peaks.chip_peak(kind); None on a CPU
        self.reduced = None            # trace_reduce.Reduced of a traced run
        self.calls = []                # CallLog.calls of the traced window
        self.window = {}               # what the runner's window returned
        self.compiles = CompileCount()

    def say(self, msg: str) -> None:
        print(f"{self.tag}[{time.monotonic() - self.t0:7.1f}s] {msg}",
              file=sys.stderr, flush=True)

    def size(self, group):
        """A group of the configuration or the mix as this run uses it: a
        rehearsal overlays each group's tiny ``rehearsal`` twin."""
        if not isinstance(group, dict):
            return group
        out = {k: self.size(v) for k, v in group.items() if k != "rehearsal"}
        if self.rehearsal and "rehearsal" in group:
            out.update(group["rehearsal"])
        return out


class CompileCount:
    """Counts programs that JAX traced and lowered (a compile, or a fetch
    from the persistent cache: either way a program that was not warm)."""

    EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"

    def __init__(self):
        self.n = 0
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, _secs, **_kw):
        if event == self.EVENT:
            self.n += 1


def correct_of(checks: dict) -> bool:
    """Every number compared is within its limit."""
    return all(c["value"] <= c["limit"] for c in checks.values())


def device_record(chips: int) -> dict:
    """The device as JAX reports it; the peak is of the fullest chip."""
    import jax

    devs = jax.devices()
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devs[:chips]]
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": int(max(peaks))}


class Tracer:
    """Takes the profiler's trace of a stretch inside the window: python
    tracing off, the window marked by a ``bench.window`` annotation, the
    call log switched on strictly inside it."""

    def __init__(self, calllog=None, start_frac: float = 0.3,
                 length_s: float = 8.0, margin_s: float = 2.0):
        self.calllog = calllog
        self.start_frac, self.length_s = start_frac, length_s
        self.margin_s = margin_s
        self._th = None
        self.error = None

    def _options(self):
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        return opts

    def start(self):
        import jax

        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        jax.profiler.start_trace(TRACE_DIR, profiler_options=self._options())

    def stop(self):
        import jax

        jax.profiler.stop_trace()

    def _body(self, seconds: float):
        import jax

        try:
            time.sleep(self.start_frac * seconds)
            length = min(self.length_s, 0.5 * seconds)
            self.start()
            with jax.profiler.TraceAnnotation("bench.window"):
                if self.calllog is not None:
                    self.calllog.on = True
                time.sleep(length)
                if self.calllog is not None:
                    self.calllog.on = False
                    # calls in flight end inside the window
                    end = time.monotonic() + self.margin_s
                    while self.calllog.inflight and time.monotonic() < end:
                        time.sleep(0.0005)
            self.stop()
        except BaseException as e:   # re-raised by finish()
            self.error = e

    def schedule(self, seconds: float):
        self._th = threading.Thread(target=self._body, args=(seconds,),
                                    daemon=True)
        self._th.start()

    def finish(self):
        self._th.join()
        if self.error is not None:
            raise self.error

    def reduce(self):
        import jax

        from . import trace_reduce

        path = trace_reduce.find_xplane(TRACE_DIR)
        events = trace_reduce.extract(path, jax.devices()[0].platform)
        return trace_reduce.Reduced(events), path


def start_jax(rehearse_cpu: int, chips: int):
    """What every entry point does before its first use of JAX: a rehearsal
    pins the CPU with enough virtual devices; the compile cache goes where
    the program's own setter puts it ($JAX_COMPILATION_CACHE_DIR, else
    <checkout>/.jax_cache: a fixed path inside the checkout). Returns the
    cache directory and whether the devices will do (a rehearsal, or TPU
    chips enough)."""
    if rehearse_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_"
            f"device_count={max(rehearse_cpu, chips)}").strip()
    from brpc_tpu.tpu.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    import jax

    devs = jax.devices()
    ok = bool(rehearse_cpu) or (devs[0].platform == "tpu"
                                and len(devs) >= chips)
    return cache_dir, ok


def fill_metrics(run: Run, result: dict, metrics: dict, device: dict,
                 tracer):
    """The line's ``metrics``: with ``--trace 0`` the cell's end-to-end
    metrics; with ``--trace 1`` its per-layer metrics, the trace's busy and
    window seconds in ``device`` and the ``breakdown``. Returns the trace
    file's path, or None."""
    if not run.args.trace:
        result["metrics"] = {
            m["name"]: {"value": metrics[m["name"]], "unit": m["unit"]}
            for m in run.end_to_end if m["name"] in metrics}
        return None
    run.reduced, path = tracer.reduce()
    device["busy_s"] = run.reduced.busy_mean_s
    device["window_s"] = run.reduced.window_s
    result["metrics"] = read_layer_metrics(run)
    result["breakdown"] = {"device_ops": run.reduced.top_ops(),
                           "idle_gaps": run.reduced.idle_gaps()}
    return path


def read_layer_metrics(run: Run) -> dict:
    """Each per-layer metric of the cell through its own reader,
    ``benchmark/layer_metrics/<name>.py:read(run)``. A metric with no
    reader is an error; a reader that finds nothing to read returns None
    and the metric is left out of the line."""
    out = {}
    for metric in run.per_layer:
        name = metric["name"]
        path = os.path.join(BENCH, "layer_metrics", name + ".py")
        if not os.path.exists(path):
            raise SystemExit(f"benchmark: per-layer metric {name!r} has no "
                             f"reader at {path}")
        spec = importlib.util.spec_from_file_location(
            "layer_metric_" + name.replace(".", "_").replace("-", "_"), path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        value = mod.read(run)
        if value is not None:
            out[name] = {"value": float(value), "unit": metric["unit"]}
    return out
