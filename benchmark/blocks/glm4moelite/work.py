"""Operations and bytes a call of the ``glm4moelite`` block needs, from its
shapes alone: of the ALGORITHM (``reference.py``'s docstring), not of an
implementation. Padding, context rows whose K and V a later chunk builds
again, padded tiles and the float32 the program widens its operands to are
not counted.

Attention. A row's K and V go through ``Wkvb`` once (prefill) or its query
and output go through ``Wuk`` and ``Wuv`` (absorbed decode): ``2 r H (nope +
vd)`` operations either way. A (query, key) pair of one head costs ``4 x
head width`` in the expanded form (prefill) and ``2 (lat + r)`` in the
absorbed form (decode: scores over the ``lat`` values of a latent row, output
over its first ``r``), and a decode step reads each live latent row once a
layer, ``2 lat`` bytes.

The experts. A row makes ``k held / E`` pairs a layer here under even routing
(0.5 at 8 of 64 held, top-4), and a launch of ``n`` pairs a layer is EXPECTED
to hit ``held (1 - (1 - 1 / held)^n)`` of the held experts, each read once.
The program's counters (``experts_hit_share``) say how even the routing is.

``m`` is the configuration's ``runner_args.model`` group: the published keys.
"""

import math

from blocks.zaya.work import launch_rows
from harness import peaks

from .reference import ATTENTION, DENSE, EXPERT, sizes

BF16, F32 = 2, 4


def _count(table, z: dict) -> int:
    return sum(math.prod(shape(z)) for _sid, shape in table.values())


def attention_weights(z: dict) -> int:
    """Wqa, Wqb, Wkva, Wkvb, Wo and the two inner norms."""
    return _count(ATTENTION, z) + z["ql"] + z["r"]


def expert_weights(z: dict) -> int:
    """One expert's parameters: gate, up and down."""
    return len(EXPERT) * z["d"] * z["ff"]


def router_weights(z: dict) -> int:
    """The router and its selection bias (stored float32)."""
    return z["d"] * z["e"] + z["e"]


def layer_parameters(z: dict, l: int, held: int) -> int:
    """Every parameter of layer ``l`` with ``held`` routed experts: layer 0
    has the dense MLP, the others the shared expert, the router and the
    routed experts; both norms."""
    own = attention_weights(z) + 2 * z["d"]
    if l == 0:
        return own + _count(DENSE, z)
    return own + (z["ns"] + held) * expert_weights(z) + router_weights(z)


def weight_count(m: dict, layers: int = None, held: int = None) -> int:
    """EVERY parameter from the shapes alone: the layers, embedding and
    untied head, the final norm. ``layers`` / ``held``: another depth or
    another count of routed experts a layer than the configuration's (the
    published 47 and 64 give the whole model)."""
    z = sizes(m)
    n = z["layers"] if layers is None else layers
    e = z["held"] if held is None else held
    return (sum(layer_parameters(z, l, e) for l in range(n))
            + 2 * z["v"] * z["d"] + z["d"])


def stored_bytes(m: dict) -> int:
    """What the configuration's storage holds: bfloat16, router and bias
    float32."""
    z = sizes(m)
    return BF16 * weight_count(m) \
        + (F32 - BF16) * (z["layers"] - 1) * router_weights(z)


def dense_bytes(z: dict) -> int:
    """The stored bytes every step reads whatever it routes: attention of
    every layer, the dense layer, every expert layer's shared expert and
    router, both norms, the head and the final norm."""
    n = z["layers"]
    return (BF16 * (n * (attention_weights(z) + 2 * z["d"])
                    + _count(DENSE, z)
                    + (n - 1) * z["ns"] * expert_weights(z)
                    + z["v"] * z["d"] + z["d"])
            + F32 * (n - 1) * router_weights(z))


def row_flops(z: dict) -> int:
    """The matmul operations of one row outside attention's scores and the
    routed experts, ALL layers: the projections (K and V through Wkvb, or
    the absorbed products: the same count), the dense layer, the shared
    experts and routers."""
    n = z["layers"]
    return 2 * (n * _count(ATTENTION, z) + _count(DENSE, z)
                + (n - 1) * (z["ns"] * expert_weights(z) + z["d"] * z["e"]))


def pairs_a_row(z: dict) -> float:
    """Pairs a row makes an expert layer HERE under even routing."""
    return z["k"] * z["held"] / z["e"]


def experts_hit(rows: float, z: dict) -> float:
    """Held experts a launch of ``rows`` rows a layer is expected to hit."""
    return z["held"] * (1.0 - (1.0 - 1.0 / z["held"])
                        ** (rows * pairs_a_row(z)))


def routed_flops(rows: float, z: dict) -> float:
    return rows * pairs_a_row(z) * 2 * expert_weights(z)


def routed_bytes(rows: float, z: dict) -> float:
    """One layer's routed product over a launch of ``rows`` rows: the
    expected experts hit once, each pair's row in and out (float32)."""
    return (BF16 * experts_hit(rows, z) * expert_weights(z)
            + 2 * F32 * rows * pairs_a_row(z) * z["d"])


def prefill_chunk_flops(n: int, start: int, m: dict, head: bool) -> int:
    """The MATMUL operations of rows ``[start, start + n)`` of a prompt:
    every projection over n rows, their expert pairs, each layer's rows over
    the keys before and among them in the expanded form, the head (one row)
    where the chunk ends its prompt."""
    z = sizes(m)
    pairs = n * start + n * (n + 1) // 2
    return int(n * row_flops(z)
               + (z["layers"] - 1) * routed_flops(n, z)
               + z["layers"] * pairs * z["h"] * 2 * (z["qk"] + z["vd"])
               + (2 * z["d"] * z["v"] if head else 0))


def prefill_flops(s: int, m: dict) -> int:
    """One prompt of s rows, however it is cut into launches."""
    return prefill_chunk_flops(s, 0, m, True)


def prefill_bytes(s: int, m: dict) -> int:
    """Weights read once (the experts the prompt is expected to hit);
    written: every layer's latent rows (bfloat16)."""
    z = sizes(m)
    return int(dense_bytes(z) + (z["layers"] - 1) * routed_bytes(s, z)
               + z["layers"] * BF16 * z["lat"] * s)


def decode_step_flops(contexts, m: dict) -> int:
    """One decode step over a batch: every dense weight times each row, the
    routed pairs, the head for each row, one row of query heads over its
    live latent rows in every layer (absorbed: ``2 (lat + r)`` a head and
    row)."""
    z = sizes(m)
    b = len(contexts)
    rows = sum(int(c) for c in contexts)
    return int(b * row_flops(z) + (z["layers"] - 1) * routed_flops(b, z)
               + z["layers"] * rows * z["h"] * 2 * (z["lat"] + z["r"])
               + 2 * b * z["d"] * z["v"])


def decode_step_bytes(contexts, m: dict) -> int:
    """The least any implementation moves: every dense weight once, each HIT
    expert once (the expectation), the live latent rows of every layer once
    (key and value at once), each row's own latent written."""
    z = sizes(m)
    b = len(contexts)
    rows = sum(int(c) for c in contexts)
    return int(dense_bytes(z) + (z["layers"] - 1) * routed_bytes(b, z)
               + z["layers"] * BF16 * z["lat"] * (rows + b))


# ------------------------------------------------------------------ kernels
def moe_expert_least_s(calls: dict, m: dict, peak: dict) -> float:
    """The least time the chip could take over the routed expert product of
    the traced launches (the kernel serves the chunks and decode alike): per
    launch and expert layer the larger of its pairs' operations over the
    bf16 peak and of ``routed_bytes`` over the HBM peak."""
    z = sizes(m)
    launches = [len(ctx) for ctx in calls["decode"]] \
        + launch_rows(calls["prefill"])
    return sum((z["layers"] - 1) * peaks.roofline_seconds(
        routed_flops(rows, z), routed_bytes(rows, z), peak)[0]
        for rows in launches)


def mla_decode_least_s(calls: dict, m: dict, peak: dict) -> float:
    """The least time the chip could take over the absorbed decode
    attention of the traced decode launches: per launch and layer the
    larger of its live latent rows' bytes (each read once, ``2 lat``) over
    the HBM peak and of ``2 H (lat + r)`` operations a row over the bf16
    peak (the bytes, at 20 heads)."""
    z = sizes(m)
    return sum(z["layers"] * peaks.roofline_seconds(
        sum(ctx) * 2 * z["h"] * (z["lat"] + z["r"]),
        sum(ctx) * BF16 * z["lat"], peak)[0]
        for ctx in calls["decode"])


# the op's name in a device trace is the kernel's ``name=``
KERNELS = {"moe_expert_roofline": (r"moe_grouped_matmul",
                                   moe_expert_least_s),
           "mla_decode_roofline": (r"mla_paged_decode",
                                   mla_decode_least_s)}
