"""Block ``sambay`` on the program's side: ``SambaYModel`` over a
``HybridStateCache`` through the program's public constructors, the programs
a schedule can reach and the direct calls that compile them, and where the
timed path left its state. The only file of the benchmark in which this
block's class names appear.
"""

from __future__ import annotations

import time
from typing import List

import numpy as np

# at import, so that a program without this architecture fails the cell at
# once (an ImportError before anything is stood up), not minutes in
from brpc_tpu.serving import hybrid_cache, hybrid_model

from harness.loadgen import Request
from harness.reference import padded, pick_sample

SCRATCH_SEQ = 10_000_000   # ids of the warm-up's own sequences


# ------------------------------------------------------------------ stand-up
def vocab(args: dict) -> int:
    """How many token ids the traffic draws from."""
    return args["model"]["vocab_size"]


def build(args: dict, seed: int):
    """``args``: the configuration's ``runner_args`` at this run's size.
    Returns the model and its cache manager, as ``ServingEngine`` takes
    them."""
    mcfg = hybrid_model.SambaYConfig(**args["model"], seed=seed % 2**32)
    kv = mcfg.cache(hybrid_cache.HybridCacheConfig(**args["kv"],
                                                   window=mcfg.window))
    return hybrid_model.SambaYModel(mcfg, kv), kv


def describe(model, kv, args: dict) -> str:
    return (f"model {args['model']} float32, {model.param_nbytes / 2**30:.2f}"
            f" GiB of weights staged matrix by matrix; cache {args['kv']}")


def release(model, kv) -> None:
    """Free the program's device state: weights, pools and slots."""
    model.close()
    kv.close()
    model._params = None
    kv.full.k_pool = kv.full.v_pool = None
    kv.window.k_pool = kv.window.v_pool = None
    kv.ssm = kv.conv = None


# ------------------------------------------------------------------- warm-up
def shapes_of(model, kv, reqs: List[Request], max_batch: int):
    """The prefill buckets, and the decode (rows, context) buckets, that
    this schedule can reach, by the program's own bucketing."""
    decode_buckets = hybrid_model.decode_buckets
    prefill_bucket = hybrid_model.prefill_bucket
    window, bs = model.config.window, kv.block_size
    pre = sorted({prefill_bucket(len(r.prompt), window) for r in reqs})
    ctx = [c for r in reqs if r.max_new > 1
           for c in (len(r.prompt) + 1, len(r.prompt) + r.max_new - 1)]
    if not ctx:
        return pre, [], []

    def buckets(b, c):
        return decode_buckets(b, [range(kv.blocks_for(c))], bs, window)

    lo, hi = buckets(1, min(ctx))[1], buckets(1, max(ctx))[1]
    lens = [l for l in (lo << i for i in range(32)) if l <= hi]
    batches = sorted({buckets(b, 1)[0] for b in range(1, max_batch + 1)})
    return pre, batches, lens


def warm_programs(model, kv, reqs: List[Request], max_batch: int,
                  say) -> int:
    """Run every program the schedule can reach once, by direct calls on the
    model instance the engine drives, on scratch sequences that are freed
    again; then start the manager's high-water marks anew. Returns how many
    ran."""
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
            rows = min(b, max_batch)
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
    return len(pre) + len(lens) * len(batches)


# ---------------------------------------------------- what the window wrote
def held_state(served, sent: List[Request], k: int, seed: int,
               pad_to: int) -> dict:
    """What k of the window's finished requests left in the manager's
    arrays: the state prefill and the decode steps WROTE while they were
    timed, read once the window has closed. The manager hands free pages and
    slots out oldest first and ``retired(seq_id)`` returns a finished
    sequence's table until one of them is handed out again. A request is
    matched to its sequence by the rows it consumed (prompt + answer - 1),
    where that count is the only one among the retired sequences; a request
    whose count is shared is left out. Returns {id(request): (rows, state)}
    with ``state`` as ``reference.state_gaps`` takes it: the first Mamba
    layer's scan state and conv tail [at the prompt's end, after the last
    row], the first window layer's K and V rows still in the ring (from
    ``ring_lo`` on) and the full layer's, (padded length, kv_dim) each."""
    import jax.numpy as jnp

    kv = served.kv
    by_rows = {}
    for sid in kv.retired_ids():
        table = kv.retired(sid)
        if table is not None and sid < SCRATCH_SEQ:
            by_rows.setdefault(table.tokens, []).append(table)

    def table_of(r):
        found = by_rows.get(len(r.prompt) + len(r.tokens) - 1, [])
        return found[0] if len(found) == 1 else None

    have = [r for r in sent if r.finished and r.tokens
            and table_of(r) is not None]
    bs, ring = kv.block_size, kv.config.ring_blocks
    out = {}
    for r in pick_sample(have, k, seed):
        table, n = table_of(r), len(r.prompt) + len(r.tokens) - 1
        pos = np.arange(padded(n, pad_to))
        live = pos < n
        full = np.where(live, np.asarray(table, np.int32)[
            np.minimum(pos // bs, len(table) - 1)] * bs + pos % bs, 0)
        ring_lo = max(0, n - ring * bs)
        in_ring = live & (pos >= ring_lo)
        rows = np.where(in_ring, np.asarray(table.window, np.int32)[
            (pos // bs) % ring] * bs + pos % bs, 0)
        out[id(r)] = (n, {
            "ssm": jnp.flip(kv.ssm[:, 0, table.slot], axis=0),
            "conv": jnp.flip(kv.conv[:, 0, table.slot], axis=0),
            "k1": kv.window.k_pool[0][rows], "v1": kv.window.v_pool[0][rows],
            "kf": kv.full.k_pool[0][full], "vf": kv.full.v_pool[0][full],
            "ring_lo": ring_lo})
    return out
