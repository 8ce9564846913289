"""Runner ``serve``: one ``Server`` + ``LlmServingService`` +
``ServingEngine`` + ``TinyTransformer`` over a ``PagedKVCache`` on
``tpu://127.0.0.1:0/0``, driven through ``LlmService.Generate`` by the
traffic mix, in this one process (the stand-up is ``chip_smoke.py``'s
``_build_serving`` / ``serving_phase``, copied, not imported).

From the program it takes the system under test, ``engine.snapshot()``,
``kv.snapshot()`` and ``GenerateResponse.ttft_us``; everything else (the
traffic, the clocks, the reduction, the reference) is the benchmark's own.
"""

from __future__ import annotations

import threading
import time
from typing import List

import numpy as np

from . import loadgen, reference
from .loadgen import Request

SCRATCH_SEQ = 10_000_000   # ids of the warm-up's own sequences


# ------------------------------------------------------------------ stand-up
class Served:
    """The system under test, stood up, and how to take it down."""

    def __init__(self, args: dict, seed: int, say):
        """``args``: the configuration's ``runner_args`` at this run's
        size."""
        from brpc_tpu import (Channel, ChannelOptions, Server, ServerOptions,
                              Stub, native)
        from brpc_tpu.proto import serving_pb2
        from brpc_tpu.serving import (EngineConfig, KVCacheConfig,
                                      LlmServingService, ModelConfig,
                                      PagedKVCache, ServingEngine,
                                      TinyTransformer)

        if native.load_dataplane() is None:
            raise SystemExit("benchmark: the native lane did not build: "
                             f"{native.dataplane_build_error()}")
        t = time.monotonic()
        mcfg = ModelConfig(**args["model"], seed=seed % 2**32)
        self.kv = PagedKVCache(KVCacheConfig(**args["kv"]), mcfg.n_layers,
                               mcfg.kv_dim)
        self.model = TinyTransformer(mcfg, self.kv)
        say(f"model {args['model']} float32 staged "
            f"({self.model.param_nbytes / 2**30:.2f} GiB by handle) in "
            f"{time.monotonic() - t:.1f}s")
        self.engine = ServingEngine(self.model, self.kv,
                                    EngineConfig(**args["engine"])).start()
        self.server = Server(ServerOptions(native_dataplane=True))
        self.server.add_service(LlmServingService(self.engine))
        self.server.start("tpu://127.0.0.1:0/0")
        ch = Channel(ChannelOptions(native_transport=True,
                                    timeout_ms=600_000))
        ch.init(str(self.server.listen_endpoint()))
        self.stub = Stub(
            ch, serving_pb2.DESCRIPTOR.services_by_name["LlmService"])
        self.max_batch = args["engine"]["max_batch"]

    def stop(self):
        """Stop serving and free the program's device state."""
        self.server.stop()
        self.server.join()
        self.engine.stop()
        self.model.close()
        self.kv.close()
        self.model._params = None
        self.kv.k_pool = self.kv.v_pool = None
        self.model = self.kv = self.engine = None


def make_sender(stub):
    """One streamed ``Generate`` as ``examples/llm_server/client.py`` does
    it, started without waiting: frames are stamped as they arrive."""
    from brpc_tpu import Controller, StreamOptions, stream_close, stream_create
    from brpc_tpu.proto import serving_pb2

    def send(r: Request, on_done):
        # a call has ended when BOTH its response and its final frame are
        # here (they travel apart), or when it failed
        lock, state = threading.Lock(), {"resp": False, "final": False,
                                         "ended": False}

        def finish():
            with lock:
                if state["ended"] or not (r.error or (state["resp"]
                                                      and state["final"])):
                    return
                state["ended"] = True
            stream_close(sid)
            r.t_done = time.monotonic()
            on_done(r)

        def on_received(_sid, msgs):
            now = time.monotonic()
            for raw in msgs:
                delta = serving_pb2.TokenDelta()
                delta.ParseFromString(raw)
                if delta.tokens:
                    r.frame_t.append(now)
                    r.streamed.extend(delta.tokens)
                if delta.done:
                    state["final"] = True
            if state["final"]:
                finish()

        sid = stream_create(StreamOptions(on_received=on_received))
        cntl = Controller()
        cntl.stream_id = sid
        cntl.timeout_ms = 600_000

        def done(c):
            if c.failed():
                r.error = f"rpc failed: {c.error_text()}"
            else:
                r.tokens = list(c.response.tokens)
                r.engine_ttft_us = int(c.response.ttft_us)
            state["resp"] = True
            finish()

        stub.Generate(
            serving_pb2.GenerateRequest(prompt_tokens=r.prompt.tolist(),
                                        max_new_tokens=r.max_new),
            controller=cntl, done=done)

    return send


# ------------------------------------------------------------------- warm-up
def _pow2(n: int) -> int:
    p = 1
    while p < n:
        p <<= 1
    return p


def prefill_bucket(s: int) -> int:
    """``TinyTransformer.prefill``'s shape bucket for a prompt of s."""
    b = max(16, _pow2(s))
    return b if b <= 128 else -(-s // 128) * 128


def shapes_of(reqs: List[Request], block: int, max_batch: int):
    """The prefill buckets, and the decode (batch, context) buckets, that
    this schedule can reach."""
    pre = sorted({prefill_bucket(len(r.prompt)) for r in reqs})
    ctx = [c for r in reqs if r.max_new > 1
           for c in (len(r.prompt) + 1, len(r.prompt) + r.max_new - 1)]
    if not ctx:
        return pre, [], []

    def lb(c):
        return max(2, _pow2(-(-c // block))) * block

    lo, hi = lb(min(ctx)), lb(max(ctx))
    lens = [l for l in (lo << i for i in range(32)) if l <= hi]
    batches = sorted({max(2, _pow2(b)) for b in range(1, max_batch + 1)})
    return pre, batches, lens


def warm_up(served: Served, reqs: List[Request], say) -> int:
    """Run every program the schedule can reach once, by direct calls on
    the model instance the engine drives (the same jitted callables), on
    scratch sequences that are freed again. Returns how many ran."""
    model, kv = served.model, served.kv
    block = kv.block_size
    pre, batches, lens = shapes_of(reqs, block, served.max_batch)
    vocab = model.config.vocab
    rng = np.random.default_rng(0)
    ran = 0
    t = time.monotonic()
    for s in pre:
        table = kv.alloc_sequence(SCRATCH_SEQ, s)
        model.prefill(rng.integers(1, vocab, size=s, dtype=np.int32), table)
        kv.free_sequence(SCRATCH_SEQ)
        ran += 1
    say(f"warm-up: {len(pre)} prefill programs {pre} in "
        f"{time.monotonic() - t:.1f}s")
    t = time.monotonic()

    def decode(b, l):
        tables = [kv.alloc_sequence(SCRATCH_SEQ + i, l) for i in range(b)]
        model.decode_step(rng.integers(1, vocab, size=b, dtype=np.int32),
                          np.full(b, l - 1, dtype=np.int32), tables)
        for i in range(b):
            kv.free_sequence(SCRATCH_SEQ + i)

    for l in lens:
        for b in batches:
            decode(b, l)
            ran += 1
    # the result's [:B] slice is a program of its own for each live B
    for b in range(1, served.max_batch + 1 if lens else 1):
        decode(b, lens[0])
    if lens:
        say(f"warm-up: {len(lens) * len(batches)} decode programs "
            f"(batch {batches} x context {lens}) in "
            f"{time.monotonic() - t:.1f}s")
    kv.assert_idle("benchmark warm-up")
    # the host path: connection, stream, service, engine loop
    send = make_sender(served.stub)
    short = min(reqs, key=lambda r: len(r.prompt))
    for i in range(2):   # two prompts that share no prefix
        r = Request(idx=-1, prompt=np.roll(short.prompt, i + 1),
                    max_new=min(2, short.max_new))
        ended = threading.Event()
        send(r, lambda _r: ended.set())
        if not ended.wait(300) or r.error:
            raise SystemExit(f"benchmark: warm-up Generate failed: {r.error}")
    return ran


# -------------------------------------------------------- spans and counters
class CallLog:
    """``prefill`` / ``prefill_suffix`` / ``decode_step`` of the model
    INSTANCE, wrapped from here while a trace is taken: a
    ``TraceAnnotation`` on the profiler's clock, and the call's sizes."""

    NAMES = {"prefill": "bench.prefill", "prefill_suffix": "bench.prefill",
             "decode_step": "bench.decode"}

    def __init__(self, model):
        self.model = model
        self.on = False
        self.calls = []    # (annotation, sizes)
        self.inflight = 0  # wrapped calls begun and not ended
        self._tls = threading.local()
        self._orig = {}

    def install(self):
        import jax

        for meth, ann in self.NAMES.items():
            orig = getattr(self.model, meth)
            self._orig[meth] = orig

            def wrapped(*a, _orig=orig, _ann=ann, _meth=meth, **kw):
                # prefill_suffix runs through decode_step: one span, the
                # outer one
                if not self.on or getattr(self._tls, "inside", False):
                    return _orig(*a, **kw)
                if _meth == "decode_step":
                    sizes = [int(p) + 1 for p in a[1]]
                else:
                    sizes = [len(a[0])]
                self._tls.inside = True
                self.inflight += 1    # only the engine's loop thread calls
                try:
                    with jax.profiler.TraceAnnotation(_ann):
                        out = _orig(*a, **kw)
                finally:
                    self._tls.inside = False
                    self.inflight -= 1
                self.calls.append((_ann, sizes))
                return out

            setattr(self.model, meth, wrapped)

    def remove(self):
        for meth in self._orig:
            delattr(self.model, meth)


class KvSampler(threading.Thread):
    """``kv.snapshot()`` every 50 ms: the highest share of blocks in use."""

    def __init__(self, kv):
        super().__init__(daemon=True)
        self.kv, self.peak, self._stop_ev = kv, 0.0, threading.Event()

    def run(self):
        while not self._stop_ev.wait(0.05):
            s = self.kv.snapshot()
            self.peak = max(self.peak, s["blocks_used"] / s["blocks_total"])

    def stop(self):
        self._stop_ev.set()
        self.join()


# --------------------------------------------------------------- the window
def measure(served: Served, mix: dict, reqs: List[Request], seconds: float,
            tracer=None) -> dict:
    """The measured window: run the mix, wait for what is in flight, and
    take the program's counters on both sides. ``tracer`` (traced runs)
    is started part-way in and stopped before the window closes."""
    send = make_sender(served.stub)
    sampler = KvSampler(served.kv)
    snap0 = served.engine.snapshot()
    sampler.start()
    if tracer is not None:
        tracer.schedule(seconds)
    out = loadgen.run_mix(mix, reqs, send, seconds)
    if tracer is not None:
        tracer.finish()
    sampler.stop()
    out["snap0"], out["snap1"] = snap0, served.engine.snapshot()
    out["kv_peak_share"] = sampler.peak
    return out


def end_to_end(sent: List[Request]) -> dict:
    """Every end-to-end number a serving window gives; the line carries
    those the cell's entries in ``BENCHMARK.json`` name."""
    m = {"ttft_p90_ms": loadgen.percentile(loadgen.ttft_ms(sent), 90)}
    gaps = loadgen.gaps_ms(sent)
    if gaps:
        m["gap_mean_ms"] = float(np.mean(gaps))
        m["gap_p95_ms"] = loadgen.percentile(gaps, 95)
        m["answer_mean_ms"] = float(np.mean(loadgen.answer_ms(sent)))
    return m


def also(sent: List[Request]) -> dict:
    """Statistics of the window that no bound is held to, in every run's
    line beside the metrics (the driver ignores the key): what a later
    benchmark PR needs to see how each would spread."""
    ttft = loadgen.ttft_ms(sent)
    return {"ttft_mean_ms": float(np.mean(ttft)),
            "ttft_p50_ms": loadgen.percentile(ttft, 50),
            "ttft_p90_ms": loadgen.percentile(ttft, 90)}


# -------------------------------------------------------------- the verdict
def pick_sample(sent: List[Request], k: int, seed: int,
                size=lambda r: len(r.prompt) + len(r.tokens)) -> List[Request]:
    """k finished requests drawn from the seed, the longest among them."""
    done = [r for r in sent if r.finished and r.tokens]
    if not done:
        return []
    longest = max(done, key=size)
    rest = [r for r in done if r is not longest]
    order = np.random.default_rng([int(seed), 9]).permutation(len(rest))
    return [longest] + [rest[i] for i in order[:max(0, k - 1)]]


def _all_tokens(r: Request) -> np.ndarray:
    return np.concatenate([r.prompt, np.asarray(r.tokens, np.int32)])


def held_rows(served: Served, sent: List[Request], k: int, seed: int,
              pad_to: int) -> dict:
    """The K and V rows that k of the window's finished requests left in
    the paged pools: what prefill and decode WROTE while they were timed,
    read once the window has closed. A finished sequence's full blocks
    stay in the pool as long as the program's own prefix cache holds them
    (the newest few hundred blocks); ``fork`` hands them to a scratch
    sequence, whose table says where they lie. Returns {id(request):
    (rows held, (K, V))}, K and V as (layers, padded length, kv_dim) device
    arrays with nought where nothing is held."""
    prefix, kv = served.engine.prefix, served.kv
    if prefix is None:
        return {}
    n_held = {id(r): prefix.match_len(_all_tokens(r))
              for r in sent if r.finished and r.tokens}
    have = [r for r in sent if n_held.get(id(r))]
    out, bs = {}, kv.block_size
    for r in pick_sample(have, k, seed, size=lambda r: n_held[id(r)]):
        toks = _all_tokens(r)
        n = prefix.fork(SCRATCH_SEQ, toks)
        table = np.asarray(kv.block_table(SCRATCH_SEQ) or [], np.int32)
        pos = np.arange(n)
        slots = np.zeros(reference.padded(len(toks) - 1, pad_to), np.int32)
        slots[:n] = table[pos // bs] * bs + pos % bs
        out[id(r)] = (n, (kv.k_pool[:, slots, :], kv.v_pool[:, slots, :]))
        kv.free_sequence(SCRATCH_SEQ)
    return out


def judge(sent: List[Request], sample: List[Request], held: dict, ref,
          mix: dict, limits: dict, control: bool = False) -> dict:
    """Every number compared, each beside its limit.

    - ``unanswered``: requests sent in the window that never ended, or
      ended in an error; limit 0.
    - ``frames_differ``: requests whose streamed frames are not exactly
      the tokens of the RPC response, or that gave another count of
      tokens than asked; limit 0.
    - ``logit_gap``: over the sample, the widest gap by which a served
      token's reference logit lies below the reference's best at that
      position (prefill for the first token, decode through the paged
      cache for the rest; the reference is a full forward over the prompt
      and the served tokens).
    - ``kv0_gap_prefill``, ``kv0_gap_decode``: over the sampled requests
      whose rows the pool still held (``held_rows``), the distance of the
      FIRST layer's K or V rows from the reference's, as a share of the
      norm of the reference's rows: the prompt's rows (written by prefill)
      and the served tokens' rows (written by decode steps). The first
      layer's rows are one matmul deep, and the reference reproduces them
      to rounding; deeper rows differ from ANY second implementation by
      the noise of bfloat16 operands (PERF.md, PR 25), so they are printed
      (``kv_gap_by_layer``) and not compared.
    - ``kv_rows_short``: how many requests short of the mix's
      ``check_kv_requests`` had rows to compare (a cell that decodes and
      found no decode row counts as one short); limit 0.
    With ``control`` the reference computed in the nearest lower precision
    is put in the program's place: its first token at every sampled
    position, its K and V rows where the program's were read."""
    unanswered = sum(1 for r in sent if not r.finished)
    differ = sum(1 for r in sent if r.finished
                 and (r.streamed != r.tokens or len(r.tokens) != r.max_new))
    new = mix["max_new_tokens"]
    rows_pad = int(new["max"] if "max" in new else new["value"])
    worst, n_tok, n_off = 0.0, 0, 0
    by_layer = {"prefill": None, "decode": None}    # (2, L): worst so far
    rows = {"prefill": 0, "decode": 0}
    for r in sample:
        logits, kv = ref.forward(r.prompt, r.tokens, rows_pad)
        tokens, got = r.tokens, held.get(id(r), (0, None))[1]
        if control:
            low, got_low = ref.forward(r.prompt, r.tokens, rows_pad,
                                       control=True)
            tokens = np.asarray(low.argmax(axis=-1))
            got = got_low if got is not None else None
        gaps = reference.token_gaps(logits, tokens)
        worst = max(worst, float(gaps.max()))
        n_tok += len(gaps)
        n_off += int((gaps > 0).sum())
        if got is None:
            continue
        n, p = held[id(r)][0], len(r.prompt)
        for part, lo, hi in (("prefill", 0, min(n, p)), ("decode", p, n)):
            if hi <= lo:
                continue
            g = reference.kv_gaps(kv, got, lo, hi)
            by_layer[part] = (g if by_layer[part] is None
                              else np.maximum(by_layer[part], g))
            rows[part] += hi - lo
    decodes = rows_pad > 1
    short = max(0, int(mix["check_kv_requests"]) - len(held))
    if decodes and not rows["decode"]:
        short = max(short, 1)
    checks = {
        "unanswered": {"value": unanswered, "limit": limits["unanswered"]},
        "frames_differ": {"value": differ, "limit": limits["frames_differ"]},
        "logit_gap": {"value": worst, "limit": limits["logit_gap"]},
        "kv_rows_short": {"value": short, "limit": limits["kv_rows_short"]},
    }
    for part in ("prefill", "decode"):
        if by_layer[part] is not None:
            checks["kv0_gap_" + part] = {
                "value": float(by_layer[part][:, 0].max()),
                "limit": limits["kv0_gap_" + part]}
    return {"checks": checks, "sample_requests": len(sample),
            "sample_tokens": n_tok, "sample_tokens_not_ref_best": n_off,
            "kv_requests": len(held), "kv_rows": rows,
            "kv_gap_by_layer": {k: v.tolist() for k, v in by_layer.items()
                                if v is not None}}


def sample_of(sent: List[Request], held: dict, k: int,
              seed: int) -> List[Request]:
    """The requests the reference runs over: k drawn from the seed with
    the longest in it, those whose K/V rows were read among them."""
    base = pick_sample(sent, k, seed)
    mine = [r for r in sent if id(r) in held]
    rest = [r for r in base[1:] if id(r) not in held]
    out = base[:1] + [r for r in mine if r is not base[0]] + rest
    return out[:max(k, len(mine) + 1)]


# ------------------------------------------------------------------ one run
def run(run) -> dict:
    """Set-up, the measured window, the verdict. Returns the result line's
    ``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` (and
    ``breakdown``), and ``checks`` to come last."""
    from . import common

    args, mix = run.args, run.size(run.mix)
    runner_args = run.size(run.cfg["runner_args"])
    model_args = runner_args["model"]
    reqs = loadgen.build_schedule(mix, args.seed, args.seconds,
                                  model_args["vocab"])
    # the reference's own weights are drawn on the host beside the
    # program's (numpy frees the interpreter), done before the window opens
    ref_weights = reference.HostWeights(args.seed % 2**32, model_args)
    served = Served(runner_args, args.seed, run.say)
    ran = warm_up(served, reqs, run.say)
    ref_weights.join()
    tracer = calllog = None
    if args.trace:
        calllog = CallLog(served.model)
        calllog.install()
        tracer = common.Tracer(calllog)
    setup_s = time.monotonic() - run.t0
    run.say(f"set-up done: {ran} programs warm, {len(reqs)} requests drawn; "
            f"window of {args.seconds}s opens")
    compiles0 = run.compiles.n
    win = measure(served, mix, reqs, args.seconds, tracer)
    compiles = run.compiles.n - compiles0
    sent = win["sent"]
    run.window = win
    metrics = {"setup_s": setup_s, **end_to_end(sent)}
    device = common.device_record(run.cell["chips"])
    run.say(f"window closed: {len(sent)} requests sent, "
            f"{sum(r.finished for r in sent)} finished, drained="
            f"{win['drained']}, generator late mean "
            f"{win['late_mean_ms']:.2f} ms max {win['late_max_ms']:.2f} ms, "
            f"{compiles} programs compiled inside the window")
    if calllog is not None:
        run.calls = calllog.calls
        calllog.remove()

    # the verdict: after the window, the peak read and the program freed
    t = time.monotonic()
    pad_to = int(mix.get("check_pad", 512))
    # a window that did not drain may still have a step in flight, whose
    # donated pools cannot be read: it is not correct anyway
    held = (held_rows(served, sent, int(mix["check_kv_requests"]),
                      args.seed, pad_to) if win["drained"] else {})
    served.stop()
    del served
    sample = sample_of(sent, held, int(mix["check_requests"]), args.seed)
    ref = reference.ServeReference(args.seed % 2**32, model_args,
                                   runner_args["reference"]["mode"],
                                   host_weights=ref_weights.get(),
                                   pad_to=pad_to)
    del ref_weights
    verdict = judge(sent, sample, held, ref, mix, run.mix["limits"])
    ref.free()
    run.say(f"reference: {verdict['sample_requests']} requests, "
            f"{verdict['sample_tokens']} served tokens "
            f"({verdict['sample_tokens_not_ref_best']} not the reference's "
            f"best), K/V rows of {verdict['kv_requests']} requests "
            f"{verdict['kv_rows']} in {time.monotonic() - t:.1f}s")

    result = {"correct": common.correct_of(verdict["checks"]),
              "attempted": len(sent),
              "failed": sum(1 for r in sent if not r.finished)}
    path = common.fill_metrics(run, result, metrics, device, tracer)
    if path:
        red = run.reduced
        run.say(f"trace: {path} window {red.window_s:.3f}s busy "
                f"{red.busy_mean_s:.3f}s, "
                f"{red.launches('bench.prefill')} prefill and "
                f"{red.launches('bench.decode')} decode launches, "
                f"{100 * red.unattributed_share():.2f}% of device time "
                f"outside every annotation")
    result["device"] = device
    result["compiles_in_window"] = compiles
    result["generator_late_ms"] = {"mean": win["late_mean_ms"],
                                   "max": win["late_max_ms"]}
    result["also"] = also(sent)
    for k in ("sample_requests", "sample_tokens",
              "sample_tokens_not_ref_best", "kv_requests", "kv_rows",
              "kv_gap_by_layer"):
        result[k] = verdict[k]
    result["checks"] = verdict["checks"]
    return result
