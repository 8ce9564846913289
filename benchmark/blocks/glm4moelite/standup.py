"""Block ``glm4moelite`` on the program's side: ``GlmMoeLiteModel`` over a
``HybridStateCache`` of latent pages through the program's public
constructors, the programs a schedule can reach and the direct calls that
compile them, and where the timed path left its state. The only file of the
benchmark in which this block's class names appear.
"""

from __future__ import annotations

import collections
from typing import List

import numpy as np

# at import, so that a program without this architecture fails the cell at
# once (an ImportError before anything is stood up), not minutes in
from brpc_tpu.serving import glm_model, hybrid_cache

from blocks.jamba import standup as chunked
from blocks.sambay.standup import SCRATCH_SEQ, release   # noqa: F401
from blocks.zaya.standup import _held
from harness.loadgen import Request
from harness.reference import padded, pick_sample


# ------------------------------------------------------------------ stand-up
def vocab(args: dict) -> int:
    """How many token ids the traffic draws from."""
    return args["model"]["vocab_size"]


def build(args: dict, seed: int):
    """``args``: the configuration's ``runner_args`` at this run's size.
    Returns the model and its cache manager, as ``ServingEngine`` takes
    them."""
    mcfg = glm_model.GlmMoeLiteConfig(**args["model"], seed=seed % 2**32)
    kv = mcfg.cache(hybrid_cache.HybridCacheConfig(**args["kv"]))
    # which chunks a long prompt is cut into follows from the engine's
    # budget: the chunked warm-up this block shares reads it from there
    chunked._BUDGET["token_budget"] = int(args["engine"]["token_budget"])
    return glm_model.GlmMoeLiteModel(mcfg, kv), kv


def describe(model, kv, args: dict) -> str:
    return (f"model {args['model']} bfloat16 (router float32), "
            f"{model.param_nbytes / 2**30:.2f} GiB of weights staged array "
            f"by array; cache {args['kv']} bfloat16 latent pages of "
            f"{kv.kv_dim} values a row allocated at "
            f"{kv.full.k_pool.shape[-1]}, no value pool; {_held()}")


# ------------------------------------------------------------------- warm-up
def warm_programs(model, kv, reqs: List[Request], max_batch: int,
                  say) -> int:
    """Every chunk and decode program the schedule can reach, run once as
    the ``jamba`` block runs its own (the chunk rule is the engine's, not a
    model's); then the model's expert counters start anew."""
    ran = chunked.warm_programs(model, kv, reqs, max_batch, say)
    model.reset_moe_counters()
    say(f"warm-up: {_held()}")
    return ran


# ---------------------------------------------------- what the window wrote
def held_state(served, sent: List[Request], k: int, seed: int,
               pad_to: int) -> dict:
    """What k of the window's finished requests (the longest among them)
    left in the manager's ONE pool: the latent rows the chunks and the
    decode steps WROTE while they were timed, read once the window has
    closed (``retired``, as the ``sambay`` block: a request is matched to its
    sequence by the rows it consumed where that count is the only one among
    the retired sequences AND among the window's requests). Returns
    {id(request): (rows, state)} with ``state`` as ``reference.state_gaps``
    takes it: layer 0's and the last layer's latent rows, (padded length,
    kv_lora + rot) each."""
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
    bs, pool = kv.block_size, kv.full.k_pool
    out = {}
    for r in pick_sample(have, k, seed):
        table, n = table_of(r), consumed(r)
        pos = np.arange(padded(n, pad_to))
        rows = np.where(pos < n, np.asarray(table, np.int32)[
            np.minimum(pos // bs, len(table) - 1)] * bs + pos % bs, 0)
        out[id(r)] = (n, {"lat0": pool[0][rows][:, :kv.kv_dim],
                          "latL": pool[-1][rows][:, :kv.kv_dim]})
    return out
