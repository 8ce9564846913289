"""Operations and bytes a call of the ``zaya`` block needs, from its shapes
alone: of the ALGORITHM (``reference.py``'s docstring), not of an
implementation. Padding, gathered-but-masked context, padded tiles,
recomputation and the float32 the program widens its operands to are not
counted.

The experts: the router sends a row to one of ``E + 1`` outputs, the last of
which computes nothing, so under even routing a launch of ``R`` rows makes
``R E / (E + 1)`` pairs a layer (6 d ff operations a pair) and is EXPECTED to
hit ``E (1 - (1 - 1 / (E + 1))^R)`` experts (12.3 of 16 at 24 rows), each
read once. The program's counters (``experts_hit_share``,
``expert_skip_share``, ``expert_rows_per_hit``) say how even the routing is.

``m`` is the configuration's ``runner_args.model`` group: the published keys.
"""

import math

from harness import peaks

from .reference import EXPERT, LAYER, ROUTER, sizes, tail_width

BF16, F32 = 2, 4
# the arrays of a layer that a row is multiplied by, outside the router
MATRICES = ("wq", "wk", "wv1", "wv2", "wo")
ROUTER_MATRICES = ("r_wd", "r_w1", "r_w2", "r_w3")


def _count(names, z: dict) -> int:
    return sum(math.prod(LAYER[k][1](z)) for k in names)


def expert_weights(z: dict) -> int:
    """One expert's parameters: gate, up and down."""
    return len(EXPERT) * z["d"] * z["ff"]


def layer_parameters(z: dict) -> int:
    """Every parameter of one layer: attention with its convs, the router,
    all the experts, both norms and both sublayers' residual scaling."""
    return _count(LAYER, z) + z["e"] * expert_weights(z)


def weight_count(m: dict) -> int:
    """EVERY parameter, from the shapes alone: the layers, the tied
    embedding once, the final norm."""
    z = sizes(m)
    return z["layers"] * layer_parameters(z) + z["v"] * z["d"] + z["d"]


def stored_bytes(m: dict) -> int:
    """What the configuration's storage holds: bfloat16, the router's
    arrays float32."""
    z = sizes(m)
    return BF16 * weight_count(m) + (F32 - BF16) * z["layers"] * _count(
        ROUTER, z)


def dense_bytes(z: dict) -> int:
    """A layer's stored bytes outside its experts."""
    return BF16 * _count([k for k in LAYER if k not in ROUTER], z) \
        + F32 * _count(ROUTER, z)


def conv_row_flops(z: dict) -> int:
    """One row of one layer's grouped conv: taps x heads x hd x hd
    multiply-adds (the one product of ``cca_mix``)."""
    return 2 * z["t1"] * (z["h"] + z["g"]) * z["hd"] * z["hd"]


def row_flops(z: dict) -> int:
    """The matmul operations of one row of one layer outside attention's
    scores and the experts: the projections, the grouped conv, the
    router."""
    return 2 * (_count(MATRICES, z) + _count(ROUTER_MATRICES, z)) \
        + conv_row_flops(z)


def pairs_a_row(z: dict) -> float:
    """Pairs a row makes a layer under even routing: the skip makes none."""
    return z["e"] / (z["e"] + 1.0)


def experts_hit(rows: float, z: dict) -> float:
    """Experts a launch of ``rows`` rows a layer is expected to hit."""
    return z["e"] * (1.0 - (1.0 - 1.0 / (z["e"] + 1.0)) ** rows)


def routed_flops(rows: float, z: dict) -> float:
    return rows * pairs_a_row(z) * 2 * expert_weights(z)


def routed_bytes(rows: float, z: dict) -> float:
    """One layer's routed product over a launch of ``rows`` rows: the
    expected experts hit once, each pair's row in and out (float32)."""
    return (BF16 * experts_hit(rows, z) * expert_weights(z)
            + 2 * F32 * rows * pairs_a_row(z) * z["d"])


def attention_pairs_flops(pairs: int, z: dict) -> int:
    """``pairs`` live (query, key) pairs of one layer, all heads: QK' over hd
    and PV over hd, 2 flops a product."""
    return pairs * z["h"] * 4 * z["hd"]


def prefill_chunk_flops(n: int, start: int, m: dict, head: bool) -> int:
    """The MATMUL operations of rows ``[start, start + n)`` of a prompt:
    every projection, conv and router over n rows, their expert pairs, each
    layer's rows over the keys before and among them, the head (one row)
    where the chunk ends its prompt."""
    z = sizes(m)
    pairs = n * start + n * (n + 1) // 2
    return int(z["layers"] * (n * row_flops(z) + routed_flops(n, z)
                              + attention_pairs_flops(pairs, z))
               + (2 * z["d"] * z["v"] if head else 0))


def prefill_flops(s: int, m: dict) -> int:
    """One prompt of s rows, however it is cut into launches."""
    return prefill_chunk_flops(s, 0, m, True)


def prefill_bytes(s: int, m: dict) -> int:
    """Weights read once (the experts the prompt is expected to hit);
    written: every layer's K/V rows (bfloat16) and its tail (float32)."""
    z = sizes(m)
    return int(z["layers"] * (dense_bytes(z) + routed_bytes(s, z)
                              + BF16 * 2 * z["kvd"] * s
                              + F32 * tail_width(z))
               + BF16 * z["v"] * z["d"])


def decode_step_flops(contexts, m: dict) -> int:
    """One decode step over a batch: every dense weight times each row, the
    routed pairs (skipped rows compute nothing), the head for each row, one
    query a row over its live rows in every layer."""
    z = sizes(m)
    b = len(contexts)
    rows = sum(int(c) for c in contexts)
    return int(z["layers"] * (b * row_flops(z) + routed_flops(b, z)
                              + attention_pairs_flops(rows, z))
               + 2 * b * z["d"] * z["v"])


def decode_step_bytes(contexts, m: dict) -> int:
    """The least any implementation moves: attention, router and head
    weights once (the tied embedding is the head), each HIT expert once (the
    expectation over the E + 1 outputs), each live row's tail read and
    written in float32, the live K/V rows of every layer. Pads of a
    gathered context are not counted."""
    z = sizes(m)
    b = len(contexts)
    rows = sum(int(c) for c in contexts)
    return int(z["layers"] * (dense_bytes(z) + routed_bytes(b, z)
                              + 2 * F32 * b * tail_width(z)
                              + BF16 * 2 * z["kvd"] * rows)
               + BF16 * z["v"] * z["d"])


# ------------------------------------------------------------------ kernels
def launch_rows(ends) -> list:
    """Rows of each prefill launch from the call log, which records a
    launch by the END of its rows: a later chunk of a prompt follows its
    earlier chunk in the log (one prompt is mid-prefill at a time) and ends
    past it, and an earlier chunk is whole units of 128 rows, so such an
    entry counts the rows past the entry before it. (A whole prompt that
    happens to follow one of a multiple of 128 rows and to be longer is
    taken for its chunk and counts fewer rows: never more than ran.)"""
    out, before = [], 0
    for end in ends:
        later = before and before % 128 == 0 and end > before
        out.append(end - before if later else end)
        before = end
    return out


def moe_expert_least_s(calls: dict, m: dict, peak: dict) -> float:
    """The least time the chip could take over the routed expert product of
    the traced launches (the kernel serves the chunks and decode alike): per
    launch and layer the larger of its pairs' operations over the bf16 peak
    and of ``routed_bytes`` over the HBM peak."""
    z = sizes(m)
    launches = [len(ctx) for ctx in calls["decode"]] \
        + launch_rows(calls["prefill"])
    return sum(z["layers"] * peaks.roofline_seconds(
        routed_flops(rows, z), routed_bytes(rows, z), peak)[0]
        for rows in launches)


# the op's name in a device trace is the kernel's ``name=``
KERNELS = {"moe_expert_roofline": (r"moe_grouped_matmul",
                                   moe_expert_least_s)}
