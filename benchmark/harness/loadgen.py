"""The one traffic generator: a mix is a data file, this reads it.

A mix (``benchmark/traffic/<name>.json``) gives the loop (``open`` with a
rate, ``closed`` with a client count), a distribution for the prompt length
and one for ``max_new_tokens``. Every seed gets the SAME multiset of sizes
and of arrival gaps -- the quantile mid-points of the distributions -- in the
SAME order, drawn from the mix's ``order_seed`` (1 where it names none): one
fixed realisation of the arrival process, replayed. ``--seed`` draws the token
ids (and the runner's weights). Near the knee the order of arrivals alone
moves the batch size, and with it every latency, by a tenth and more (PERF.md,
PR 25): an order drawn from ``--seed`` would change the work with the seed,
and runs of different seeds could not be compared.

The program receives only ``prompt_tokens`` and ``max_new_tokens``.
"""

from __future__ import annotations

import math
import threading
import time
from dataclasses import dataclass, field
from statistics import NormalDist
from typing import Callable, List, Optional

import numpy as np


# --------------------------------------------------------------- the schedule
@dataclass
class Request:
    idx: int
    prompt: np.ndarray            # int32 token ids, 1..vocab-1
    max_new: int
    due_s: Optional[float] = None   # open loop: offset from the window's start
    client: int = 0                 # closed loop: which client sends it
    # ---- filled in by the run
    t_due: float = 0.0            # monotonic; open loop: when it was due
    t_sent: float = 0.0
    frame_t: List[float] = field(default_factory=list)
    streamed: List[int] = field(default_factory=list)
    tokens: List[int] = field(default_factory=list)   # the RPC response's
    engine_ttft_us: int = 0
    t_done: float = 0.0
    error: str = ""

    @property
    def finished(self) -> bool:
        return self.t_done > 0 and not self.error


def _quantile_points(n: int) -> np.ndarray:
    return (np.arange(n, dtype=np.float64) + 0.5) / n


def draw_sizes(dist: dict, n: int) -> np.ndarray:
    """n whole numbers at the quantile mid-points of ``dist`` (sorted)."""
    kind = dist["dist"]
    u = _quantile_points(n)
    if kind == "const":
        vals = np.full(n, float(dist["value"]))
    elif kind == "uniform":
        vals = dist["min"] + u * (dist["max"] - dist["min"])
    elif kind == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(x)) for x in u])
        vals = np.exp(math.log(dist["median"]) + dist["sigma"] * z)
    else:
        raise ValueError(f"traffic: unknown dist {kind!r}")
    lo = dist.get("min", -math.inf)
    hi = dist.get("max", math.inf)
    return np.clip(np.rint(vals), lo, hi).astype(np.int64)


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), stream])


def build_schedule(mix: dict, seed: int, seconds: float,
                   vocab: int) -> List[Request]:
    """The requests of one run. Open loop: ``round(rate * seconds)``
    requests, due at the cumulated (shuffled) exponential quantile gaps,
    scaled so the last is due just inside the window. Closed loop:
    ``cycle`` sizes per client, which a client walks round until the
    window closes."""
    loop = mix["loop"]
    if loop == "open":
        n = max(1, int(round(mix["rate_per_s"] * seconds)))
    elif loop == "closed":
        n = int(mix["clients"]) * int(mix["cycle"])
    else:
        raise ValueError(f"traffic: unknown loop {loop!r}")
    plen = draw_sizes(mix["prompt_len"], n)
    nnew = draw_sizes(mix["max_new_tokens"], n)
    order = int(mix.get("order_seed", 1))
    _rng(order, 1).shuffle(plen)
    _rng(order, 2).shuffle(nnew)
    tok_rng = _rng(seed, 3)
    reqs = [Request(idx=i,
                    prompt=tok_rng.integers(1, vocab, size=int(plen[i]),
                                            dtype=np.int32),
                    max_new=int(nnew[i])) for i in range(n)]
    if loop == "open":
        gaps = -np.log1p(-_quantile_points(n))
        _rng(order, 4).shuffle(gaps)
        due = np.cumsum(gaps)
        due *= seconds * (1.0 - 0.5 / n) / due[-1]
        for r, d in zip(reqs, due):
            r.due_s = float(d)
    else:
        for r in reqs:
            r.client = r.idx % int(mix["clients"])
    return reqs


# ------------------------------------------------------------------- the run
# send(request, on_done) starts one call and returns at once; frames and the
# completion are written into the request by the sender's callbacks, and
# on_done(request) is called when the call has ended (well or not).
Sender = Callable[[Request, Callable[[Request], None]], None]


class _Pending:
    def __init__(self):
        self._cv = threading.Condition()
        self._n = 0

    def add(self):
        with self._cv:
            self._n += 1

    def done(self, _req=None):
        with self._cv:
            self._n -= 1
            self._cv.notify_all()

    def wait(self, timeout: float) -> bool:
        end = time.monotonic() + timeout
        with self._cv:
            while self._n > 0:
                left = end - time.monotonic()
                if left <= 0:
                    return False
                self._cv.wait(left)
        return True


def run_open(reqs: List[Request], send: Sender, seconds: float,
             drain_s: float = 60.0, clock=time.monotonic,
             sleep=time.sleep) -> dict:
    """Send each request at its due time, whether or not earlier ones have
    answered; every request is timed from when it was DUE. Returns when
    all have ended or ``drain_s`` after the window closed."""
    pending = _Pending()
    t0 = clock()
    late = []
    for r in reqs:
        r.t_due = t0 + r.due_s
        wait = r.t_due - clock()
        if wait > 0:
            sleep(wait)
        r.t_sent = clock()
        late.append(r.t_sent - r.t_due)
        pending.add()
        send(r, pending.done)
    wait = t0 + seconds - clock()
    if wait > 0:
        sleep(wait)
    t_close = clock()
    drained = pending.wait(drain_s)
    return {"t0": t0, "t_close": t_close, "sent": list(reqs),
            "drained": drained,
            "late_mean_ms": 1e3 * float(np.mean(late)),
            "late_max_ms": 1e3 * float(np.max(late))}


def run_closed(reqs: List[Request], send: Sender, seconds: float,
               clients: int, drain_s: float = 60.0,
               clock=time.monotonic) -> dict:
    """``clients`` callers, each sending its next request when the last
    answered, until the window closes; a request is timed from its send.
    Each client walks round its own share of ``reqs``: a request sent again
    is a fresh copy with its tokens rolled."""
    t0 = clock()
    t_end = t0 + seconds
    sent: List[List[Request]] = [[] for _ in range(clients)]

    def client(c: int):
        mine = [r for r in reqs if r.client == c]
        i = 0
        while clock() < t_end:
            src = mine[i % len(mine)]
            # round again with the tokens rolled: same sizes, no shared prefix
            r = Request(idx=src.idx, prompt=np.roll(src.prompt, i // len(mine)),
                        max_new=src.max_new, client=c)
            i += 1
            ended = threading.Event()
            r.t_sent = r.t_due = clock()
            sent[c].append(r)
            send(r, lambda _r: ended.set())
            if not ended.wait(drain_s + seconds):
                r.error = r.error or "no answer"
                return

    threads = [threading.Thread(target=client, args=(c,), daemon=True)
               for c in range(clients)]
    for th in threads:
        th.start()
    for th in threads:
        th.join(seconds + 2 * drain_s)
    drained = not any(th.is_alive() for th in threads)
    allsent = sorted((r for s in sent for r in s), key=lambda r: r.t_sent)
    return {"t0": t0, "t_close": t_end, "sent": allsent, "drained": drained,
            "late_mean_ms": 0.0, "late_max_ms": 0.0}


def run_mix(mix: dict, reqs: List[Request], send: Sender,
            seconds: float) -> dict:
    if mix["loop"] == "open":
        return run_open(reqs, send, seconds)
    return run_closed(reqs, send, seconds, int(mix["clients"]))


# --------------------------------------------------------------- the metrics
def percentile(values, q: float) -> float:
    """The q-th percentile by the nearest-rank rule over ALL values; a
    missing value (inf) is beyond every percentile."""
    v = sorted(values)
    if not v:
        return math.inf
    k = max(0, min(len(v) - 1, int(math.ceil(q / 100.0 * len(v))) - 1))
    return v[k]


def ttft_ms(reqs: List[Request]) -> List[float]:
    """Due (open) or send (closed) time to the first token frame; a failed
    or unanswered request counts as infinitely late."""
    return [1e3 * (r.frame_t[0] - r.t_due) if r.frame_t and not r.error
            else math.inf for r in reqs]


def answer_ms(reqs: List[Request]) -> List[float]:
    """Due (open) or send (closed) time to the LAST token frame: the whole
    answer; a failed or unanswered request counts as infinitely late."""
    return [1e3 * (r.frame_t[-1] - r.t_due) if r.finished and r.frame_t
            else math.inf for r in reqs]


def gaps_ms(reqs: List[Request]) -> List[float]:
    """Every gap between consecutive token frames of every request."""
    out: List[float] = []
    for r in reqs:
        t = r.frame_t
        out.extend(1e3 * (t[i + 1] - t[i]) for i in range(len(t) - 1))
    return out
