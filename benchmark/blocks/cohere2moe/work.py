"""Operations and bytes a call of the ``cohere2moe`` block needs, from its
shapes alone: of the ALGORITHM (``reference.py``'s docstring) on this chip's
share, not of an implementation. Padding, gathered-but-masked context,
padded tiles and recomputation are not counted.

The routed experts: a row makes ``k * held / experts`` pairs here on average
(1 at the published 8 x 16 / 128), 6 d ff operations a pair; a launch of P
pairs a layer is EXPECTED to hit ``held * (1 - (1 - 1 / held)^P)`` of the
held experts under even routing (10.3 of 16 at 16 pairs, 6.5 at 8), and reads
each of those once. The program's counters (``experts_hit_share``) say how
even the routing is.

``m`` is the configuration's ``runner_args.model`` group.
"""

from harness import peaks

from .reference import sizes

BF16, F32 = 2, 4


def dense_layer_weights(z: dict) -> int:
    """A layer's parameters every row is multiplied by: attention and the
    shared experts (bfloat16)."""
    return (2 * z["d"] * z["qd"] + 2 * z["d"] * z["kvd"]
            + z["ns"] * 3 * z["d"] * z["ff"])


def expert_weights(z: dict) -> int:
    return 3 * z["d"] * z["ff"]


def pairs_a_row(z: dict) -> float:
    return z["k"] * z["held"] / z["experts"]


def experts_hit(pairs: float, z: dict) -> float:
    """Held experts a launch of ``pairs`` pairs a layer is expected to hit."""
    return z["held"] * (1.0 - (1.0 - 1.0 / z["held"]) ** pairs)


def weight_count(m: dict) -> int:
    """Every parameter the chip holds: a test holds it to the staged bytes."""
    z = sizes(m)
    return (z["layers"] * (dense_layer_weights(z) + z["d"] * z["experts"]
                           + z["held"] * expert_weights(z))
            + z["v"] * z["d"])


def attention_pairs_flops(pairs: int, z: dict) -> int:
    """QK' and PV over ``pairs`` live (query, key) pairs of one layer, all
    query heads: 2 products x 2 flops x hd a head."""
    return pairs * z["h"] * 4 * z["hd"]


def routed_flops(rows: float, z: dict) -> float:
    return rows * pairs_a_row(z) * 2 * expert_weights(z)


def routed_bytes(rows: float, z: dict) -> float:
    """One layer's routed product over a launch of ``rows`` rows: the
    expected experts hit once, each pair's row in and out (float32)."""
    pairs = rows * pairs_a_row(z)
    return (BF16 * experts_hit(pairs, z) * expert_weights(z)
            + 2 * F32 * pairs * z["d"])


def prefill_flops(s: int, m: dict) -> int:
    """One prompt of s rows: every layer over s rows (window layers see at
    most ``window`` keys a row), the router, the routed pairs, the head for
    the last row."""
    z = sizes(m)
    banded = sum(min(t + 1, z["window"]) for t in range(s))
    total = 0.0
    for kind in z["kinds"]:
        total += 2 * s * (dense_layer_weights(z) + z["d"] * z["experts"])
        total += routed_flops(s, z)
        total += attention_pairs_flops(
            banded if kind == "window" else s * (s + 1) // 2, z)
    return int(total + 2 * z["d"] * z["v"])


def prefill_bytes(s: int, m: dict) -> int:
    """Weights read once (the held experts the prompt is expected to hit),
    written: the rows of the window layers that stay in the ring and the
    full layers' rows (bfloat16)."""
    z = sizes(m)
    kept = min(s, z["window"])
    rows = sum(kept if kind == "window" else s for kind in z["kinds"])
    dense = z["layers"] * (BF16 * dense_layer_weights(z)
                           + F32 * z["d"] * z["experts"])
    return int(dense + z["layers"] * routed_bytes(s, z)
               + BF16 * (z["v"] * z["d"] + 2 * z["kvd"] * rows))


def live_rows(contexts, z: dict) -> int:
    """K/V rows a step reads: window layers at most ``window`` of a
    sequence's, full layers all of them."""
    return sum(min(int(c), z["window"]) if kind == "window" else int(c)
               for kind in z["kinds"] for c in contexts)


def decode_step_flops(contexts, m: dict) -> int:
    """One decode step over a batch: every dense weight times each row, the
    routed pairs, the head for each row, one query a row over its live
    rows."""
    z = sizes(m)
    b = len(contexts)
    dense = z["layers"] * (dense_layer_weights(z) + z["d"] * z["experts"]) \
        + z["d"] * z["v"]
    return int(2 * b * dense + z["layers"] * routed_flops(b, z)
               + attention_pairs_flops(live_rows(contexts, z), z))


def decode_step_bytes(contexts, m: dict) -> int:
    """Every dense weight read once (the tied embedding is the head), the
    held experts the batch is expected to hit once, the live K/V rows of
    each sequence (bfloat16). Pads of a gathered context are not counted."""
    z = sizes(m)
    dense = z["layers"] * (BF16 * dense_layer_weights(z)
                           + F32 * z["d"] * z["experts"]) \
        + BF16 * z["v"] * z["d"]
    return int(dense + z["layers"] * routed_bytes(len(contexts), z)
               + BF16 * 2 * z["kvd"] * live_rows(contexts, z))


# ------------------------------------------------------------------ kernels
def moe_expert_least_s(calls: dict, m: dict, peak: dict) -> float:
    """The least time the chip could take over the routed expert product of
    the traced launches (the kernel serves prefill and decode alike): per
    launch and layer the larger of its pairs' operations over the bf16 peak
    and of ``routed_bytes`` over the HBM peak."""
    z = sizes(m)
    launches = [len(ctx) for ctx in calls["decode"]] + list(calls["prefill"])
    return sum(z["layers"] * peaks.roofline_seconds(
        routed_flops(rows, z), routed_bytes(rows, z), peak)[0]
        for rows in launches)


# the op's name in a device trace is the kernel's ``name=``
KERNELS = {"moe_expert_roofline": (r"moe_grouped_matmul",
                                   moe_expert_least_s)}
