"""Block ``jamba`` on the program's side: ``JambaModel`` over a
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
from brpc_tpu.serving import hybrid_cache, jamba_model

from blocks.sambay.standup import SCRATCH_SEQ, release   # noqa: F401
from harness.loadgen import Request
from harness.reference import padded, pick_sample

# the engine's per-step budget of the configuration that was stood up last:
# which chunks a long prompt is cut into follows from it, and
# ``warm_programs`` is handed the model and the cache only
_BUDGET = {}


# ------------------------------------------------------------------ stand-up
def vocab(args: dict) -> int:
    """How many token ids the traffic draws from."""
    return args["model"]["vocab_size"]


def build(args: dict, seed: int):
    """``args``: the configuration's ``runner_args`` at this run's size.
    Returns the model and its cache manager, as ``ServingEngine`` takes
    them."""
    mcfg = jamba_model.JambaConfig(**args["model"], seed=seed % 2**32)
    kv = mcfg.cache(hybrid_cache.HybridCacheConfig(**args["kv"]))
    _BUDGET["token_budget"] = int(args["engine"]["token_budget"])
    return jamba_model.JambaModel(mcfg, kv), kv


def describe(model, kv, args: dict) -> str:
    return (f"model {args['model']} bfloat16, "
            f"{model.param_nbytes / 2**30:.2f} GiB of weights staged array "
            f"by array; cache {args['kv']} bfloat16 pages, float32 slots")


# ------------------------------------------------------------------- warm-up
def chunks_of(prompt_len: int, rows: int):
    """(start, end) of the launches that prefill a prompt where a step may
    prefill ``rows`` rows: the whole prompt where it fits, else chunks of
    ``rows`` and the rest."""
    if prompt_len <= rows:
        return [(0, prompt_len)]
    return [(a, min(prompt_len, a + rows))
            for a in range(0, prompt_len, rows)]


def shapes_of(model, kv, reqs: List[Request], max_batch: int):
    """The chunk programs (one example ``(start, end)`` a program), and the
    decode (rows, context) buckets, that this schedule can reach, by the
    program's own bucketing and the engine's rule for a step's chunk: what
    the budget leaves beside the running rows, in whole units."""
    unit = int(np.lcm(model.PREFILL_GRANULE, kv.block_size))
    budget = _BUDGET["token_budget"]
    sizes = sorted({max(unit, (budget - b) // unit * unit)
                    for b in range(max_batch + 1)})
    pre = {}
    for r in reqs:
        for rows in sizes:
            for a, b in chunks_of(len(r.prompt), rows):
                pre.setdefault(model._chunk_buckets(b - a, b, a), (a, b))
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
    again; then start the manager's high-water marks anew. Returns how many
    ran."""
    pre, batches, lens = shapes_of(model, kv, reqs, max_batch)
    vocab = model.config.vocab
    rng = np.random.default_rng(0)
    t = time.monotonic()
    for (_c, _l), (a, b) in sorted(pre.items()):
        table = kv.alloc_sequence(SCRATCH_SEQ, b)
        model.prefill_suffix(rng.integers(1, vocab, size=b, dtype=np.int32),
                             table, a)
        kv.free_sequence(SCRATCH_SEQ)
    say(f"warm-up: {len(pre)} chunk programs (rows, context) {sorted(pre)} "
        f"in {time.monotonic() - t:.1f}s")
    t = time.monotonic()
    short = kv.block_size + 1
    for l in lens:
        for b in batches:
            rows = min(b, max_batch, kv.config.max_sequences)
            # ONE row at the bucket's context: a batch of them need not fit
            # the pool, and the longest row alone sets the bucket
            ctx = [l] + [min(short, l)] * (rows - 1)
            tables = [kv.alloc_sequence(SCRATCH_SEQ + i, c)
                      for i, c in enumerate(ctx)]
            model.decode_step(
                rng.integers(1, vocab, size=rows, dtype=np.int32),
                np.asarray(ctx, np.int32) - 1, tables)
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
    """What k of the window's finished requests (the longest among them)
    left in the manager's arrays: the state the chunks and the decode steps
    WROTE while they were timed, read once the window has closed
    (``retired``, as the ``sambay`` block: a request is matched to its
    sequence by the rows it consumed where that count is the only one among
    the retired sequences AND among the window's requests). Returns
    {id(request): (rows, state)} with ``state`` as ``reference.state_gaps``
    takes it: the first and the last Mamba layer's scan state and conv tail
    [at the prompt's end, after the last row], the first attention layer's K
    and V rows, (padded length, kv_dim) each."""
    import jax.numpy as jnp

    kv = served.kv
    by_rows = {}
    for sid in kv.retired_ids():
        table = kv.retired(sid)
        if table is not None and sid < SCRATCH_SEQ:
            by_rows.setdefault(table.tokens, []).append(table)

    def consumed(r):
        return len(r.prompt) + len(r.tokens) - 1

    shared = collections.Counter(consumed(r) for r in sent if r.tokens)

    def table_of(r):
        found = by_rows.get(consumed(r), [])
        return (found[0] if len(found) == 1 and shared[consumed(r)] == 1
                else None)

    have = [r for r in sent if r.finished and r.tokens
            and table_of(r) is not None]
    bs = kv.block_size
    out = {}
    for r in pick_sample(have, k, seed):
        table, n = table_of(r), consumed(r)
        pos = np.arange(padded(n, pad_to))
        full = np.where(pos < n, np.asarray(table, np.int32)[
            np.minimum(pos // bs, len(table) - 1)] * bs + pos % bs, 0)
        out[id(r)] = (n, {
            "ssm0": jnp.flip(kv.ssm[:, 0, table.slot], axis=0),
            "conv0": jnp.flip(kv.conv[:, 0, table.slot], axis=0),
            "ssmL": jnp.flip(kv.ssm[:, -1, table.slot], axis=0),
            "convL": jnp.flip(kv.conv[:, -1, table.slot], axis=0),
            "kf": kv.full.k_pool[0][full], "vf": kv.full.v_pool[0][full]})
    return out
