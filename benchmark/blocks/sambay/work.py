"""Operations and bytes a call of the ``sambay`` block needs, from its shapes
alone: of the ALGORITHM (``reference.py``'s docstring), not of an
implementation. Prefill runs layers ``0 .. N/2 + 1`` over all rows and the
cross-decoder for the last row only; padding, gathered-but-masked context and
recomputation are not counted.

``m`` is the configuration's ``runner_args.model`` group: the published keys
(``hidden_size``, ``num_attention_heads``, ...) and the assumed Mamba sizes.
"""

from .reference import layer_kinds, sizes

F32 = 4


def mixer_weights(kind: str, z: dict) -> int:
    """Parameters of one layer's mixer that a row is multiplied by."""
    d, di = z["d"], z["di"]
    return {"mamba": d * 2 * di + di * (z["r"] + 2 * z["n"]) + z["r"] * di
            + di * d,
            "window": d * (d + 2 * z["kvd"]) + d * d,
            "full": d * (d + 2 * z["kvd"]) + d * d,
            "gmu": 2 * d * di, "cross": 2 * d * d}[kind]


def layer_weights(kind: str, z: dict) -> int:
    return mixer_weights(kind, z) + 3 * z["d"] * z["ff"]


def weight_count(m: dict) -> int:
    """Every parameter a decode step reads: the layers' matrices, the conv
    and scan parameters, the tied embedding once (norms and biases are
    thousands and left out)."""
    z = sizes(m)
    kinds = layer_kinds(z["layers"])
    scan = z["di"] * (z["kc"] + z["n"] + 2)      # conv, A, D, dt bias
    return (sum(layer_weights(k, z) for k in kinds)
            + kinds.count("mamba") * scan + z["v"] * z["d"])


def attention_pairs_flops(pairs: int, z: dict) -> int:
    """Differential attention over ``pairs`` live (query, key) pairs of one
    layer, all heads: each of the H softmax maps does QK' over hd and PV over
    the 2 hd-wide v, 2 flops a product."""
    return pairs * z["h"] * 6 * z["hd"]


def scan_row_flops(z: dict) -> int:
    """One row of one Mamba layer outside its matmuls: the conv, and per
    (channel, state) element exp, decay, drive, sum and the C contraction."""
    return z["di"] * (2 * z["kc"] + 7 * z["n"])


def prefill_flops(s: int, m: dict) -> int:
    """One prompt of s rows: the self-decoder's layers over s rows (window
    layers see at most ``window`` keys a row, the full layer all earlier
    rows), the cross-decoder and the head for the LAST row only."""
    z = sizes(m)
    kinds = layer_kinds(z["layers"])
    half = z["layers"] // 2
    w = z["window"]
    banded = sum(min(t + 1, w) for t in range(s))
    total = 0
    for l, kind in enumerate(kinds):
        rows = s if l <= half + 1 else 1
        total += 2 * rows * layer_weights(kind, z)
        if kind == "mamba":
            total += rows * scan_row_flops(z)
        elif kind == "window":
            total += attention_pairs_flops(banded, z)
        elif kind == "full":
            total += attention_pairs_flops(s * (s + 1) // 2, z)
        elif kind == "cross":
            total += attention_pairs_flops(s, z)
    return total + 2 * z["d"] * z["v"]


def state_bytes_row(z: dict, kinds: list) -> int:
    """The recurrent state of one sequence: every Mamba layer's scan state
    and conv tail."""
    return F32 * kinds.count("mamba") * z["di"] * (z["n"] + z["kc"] - 1)


def prefill_bytes(s: int, m: dict) -> int:
    """Weights read once; written: the recurrent state, the rows of the
    window layers that stay in the ring, the full layer's rows."""
    z = sizes(m)
    kinds = layer_kinds(z["layers"])
    kept = min(s, z["window"])
    rows = 2 * z["kvd"] * (kinds.count("window") * kept + s)
    return F32 * (weight_count(m) + rows) + state_bytes_row(z, kinds)


def decode_step_flops(contexts, m: dict) -> int:
    """One decode step over a batch: every weight times each row, the head
    for each row, one query a row over the window (window layers) or the
    whole live context (the full layer and every cross layer)."""
    z = sizes(m)
    kinds = layer_kinds(z["layers"])
    b = len(contexts)
    shared = kinds.count("full") + kinds.count("cross")
    pairs = sum(kinds.count("window") * min(int(c), z["window"])
                + shared * int(c) for c in contexts)
    return (2 * b * weight_count(m)
            + b * kinds.count("mamba") * scan_row_flops(z)
            + attention_pairs_flops(pairs, z))


def decode_step_bytes(contexts, m: dict) -> int:
    """Every weight read once (the tied embedding is the head), plus the
    live state of each sequence: the recurrent state read and written, the
    window layers' live rows, and the full layer's live rows once for each
    layer that attends over them (it and the cross layers: the K/V is stored
    once, each layer's attention still has to read it). Pads of a gathered
    context are not counted."""
    z = sizes(m)
    kinds = layer_kinds(z["layers"])
    shared = kinds.count("full") + kinds.count("cross")
    rows = sum(kinds.count("window") * min(int(c), z["window"])
               + shared * int(c) for c in contexts)
    return (F32 * (weight_count(m) + 2 * z["kvd"] * rows)
            + 2 * len(contexts) * state_bytes_row(z, kinds))


# no kernel of this block's own: its prefill calls the flash forward kernel
# the ``tiny`` block's ``flash_prefill_roofline`` reads, and the cell lists
# itself under no kernel metric
KERNELS = {}
