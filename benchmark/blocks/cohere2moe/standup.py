"""Block ``cohere2moe`` on the program's side: ``Cohere2MoeModel`` over a
``HybridStateCache`` through the program's public constructors, the programs
a schedule can reach and the direct calls that compile them, and where the
timed path left its state. The only file of the benchmark in which this
block's class names appear.
"""

from __future__ import annotations

import collections
import time
from typing import List

import numpy as np

# at import, so that a program without this architecture fails the cell at
# once (an ImportError before anything is stood up), not minutes in
from brpc_tpu.serving import hybrid_cache, hybrid_model, moe_model

from blocks.sambay.standup import SCRATCH_SEQ, release   # noqa: F401
from harness.loadgen import Request
from harness.reference import padded, pick_sample


# ------------------------------------------------------------------ stand-up
def vocab(args: dict) -> int:
    """How many token ids the traffic draws from: the vocabulary's slice."""
    return args["model"]["vocab_size"]


def build(args: dict, seed: int):
    """``args``: the configuration's ``runner_args`` at this run's size.
    Returns the model and its cache manager, as ``ServingEngine`` takes
    them."""
    mcfg = moe_model.Cohere2MoeConfig(**args["model"], seed=seed % 2**32)
    kv = mcfg.cache(hybrid_cache.HybridCacheConfig(**args["kv"],
                                                   window=mcfg.window))
    return moe_model.Cohere2MoeModel(mcfg, kv), kv


def describe(model, kv, args: dict) -> str:
    return (f"model {args['model']} bfloat16, "
            f"{model.param_nbytes / 2**30:.2f} GiB of weights staged matrix "
            f"by matrix; cache {args['kv']} bfloat16")


# ------------------------------------------------------------------- warm-up
def shapes_of(model, kv, reqs: List[Request], max_batch: int):
    """The prefill buckets, and the decode (rows, context) buckets, that
    this schedule can reach, by the program's own bucketing."""
    cfg, bs = model.config, kv.block_size
    pre = sorted({hybrid_model.prefill_bucket(len(r.prompt), cfg.window)
                  for r in reqs})
    ctx = [c for r in reqs if r.max_new > 1
           for c in (len(r.prompt) + 1, len(r.prompt) + r.max_new - 1)]
    if not ctx:
        return pre, [], []

    def buckets(b, c):
        return model._decode_buckets(b, [range(kv.blocks_for(c))])

    lo, hi = buckets(1, min(ctx))[1], buckets(1, max(ctx))[1]
    lens = [l for l in (lo << i for i in range(32)) if l <= hi]
    batches = sorted({buckets(b, 1)[0] for b in range(1, max_batch + 1)})
    return pre, batches, lens


def warm_programs(model, kv, reqs: List[Request], max_batch: int,
                  say) -> int:
    """Run every program the schedule can reach once, by direct calls on the
    model instance the engine drives, on scratch sequences that are freed
    again; then start the manager's high-water marks and the model's expert
    counters anew. Returns how many ran."""
    pre, batches, lens = shapes_of(model, kv, reqs, max_batch)
    vocab = model.config.vocab
    rng = np.random.default_rng(0)
    t = time.monotonic()
    for s in pre:
        table = kv.alloc_sequence(SCRATCH_SEQ, s)
        model.prefill(rng.integers(1, vocab, size=s, dtype=np.int32), table)
        kv.free_sequence(SCRATCH_SEQ)
    say(f"warm-up: {len(pre)} prefill programs {pre} in "
        f"{time.monotonic() - t:.1f}s")
    t = time.monotonic()
    for l in lens:
        for b in batches:
            rows = min(b, max_batch, kv.config.max_sequences)
            tables = [kv.alloc_sequence(SCRATCH_SEQ + i, l)
                      for i in range(rows)]
            model.decode_step(
                rng.integers(1, vocab, size=rows, dtype=np.int32),
                np.full(rows, l - 1, dtype=np.int32), tables)
            for i in range(rows):
                kv.free_sequence(SCRATCH_SEQ + i)
    if lens:
        say(f"warm-up: {len(lens) * len(batches)} decode programs "
            f"(rows {batches} x context {lens}) in "
            f"{time.monotonic() - t:.1f}s")
    kv.assert_idle("benchmark warm-up")
    kv.reset_peak()
    model.reset_moe_counters()
    return len(pre) + len(lens) * len(batches)


# ---------------------------------------------------- what the window wrote
def held_state(served, sent: List[Request], k: int, seed: int,
               pad_to: int) -> dict:
    """What k of the window's finished requests left in the manager's pools:
    the rows prefill and the decode steps WROTE while they were timed, read
    once the window has closed (``retired``, as the ``sambay`` block: a
    request is matched to its sequence by the rows it consumed where that
    count is the only one among the retired sequences AND among the window's
    requests). Returns {id(request): (rows, state)}: the first
    window layer's K and V rows still in the ring (from ``ring_lo`` on) and
    the first full layer's, (padded length, kv_dim) each."""
    kv = served.kv
    by_rows = {}
    for sid in kv.retired_ids():
        table = kv.retired(sid)
        if table is not None and sid < SCRATCH_SEQ:
            by_rows.setdefault(table.tokens, []).append(table)

    def consumed(r):
        return len(r.prompt) + len(r.tokens) - 1

    # a count shared by two requests names neither: the other's sequence
    # may be the one still retired
    shared = collections.Counter(consumed(r) for r in sent if r.tokens)

    def table_of(r):
        found = by_rows.get(consumed(r), [])
        return (found[0] if len(found) == 1 and shared[consumed(r)] == 1
                else None)

    have = [r for r in sent if r.finished and r.tokens
            and table_of(r) is not None]
    bs, ring = kv.block_size, kv.config.ring_blocks
    out = {}
    for r in pick_sample(have, k, seed):
        table, n = table_of(r), consumed(r)
        pos = np.arange(padded(n, pad_to))
        live = pos < n
        full = np.where(live, np.asarray(table, np.int32)[
            np.minimum(pos // bs, len(table) - 1)] * bs + pos % bs, 0)
        ring_lo = max(0, n - ring * bs)
        in_ring = live & (pos >= ring_lo)
        rows = np.where(in_ring, np.asarray(table.window, np.int32)[
            (pos // bs) % ring] * bs + pos % bs, 0)
        out[id(r)] = (n, {
            "k0": kv.window.k_pool[0][rows], "v0": kv.window.v_pool[0][rows],
            "kf": kv.full.k_pool[0][full], "vf": kv.full.v_pool[0][full],
            "ring_lo": ring_lo})
    return out
