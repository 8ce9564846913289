"""Operations and bytes a call of the ``jamba`` block needs, from its shapes
alone: of the ALGORITHM (``reference.py``'s docstring), not of an
implementation. Padding, gathered-but-masked context, recomputation and the
float32 the program widens its operands to are not counted.

``m`` is the configuration's ``runner_args.model`` group: the published keys.
"""

import math

from .reference import ATTN, EVERY, MAMBA, shapes, sizes

BF16, F32 = 2, 4
# the arrays of a layer that a row is multiplied by (the rest are vectors
# and the scan's and the conv's parameters)
MATRICES = {"win", "wx", "wdt", "wout", "wq", "wk", "wv", "wo", "wg", "wu",
            "wd"}


def _count(names, z, only=None) -> int:
    sh = shapes(z)
    return sum(math.prod(sh[k]) for k in names
               if only is None or k in only)


def layer_parameters(kind: str, z: dict, only=None) -> int:
    """Parameters of one layer with its MLP and both norms."""
    return _count((MAMBA if kind == "mamba" else ATTN) + EVERY, z, only)


def weight_count(m: dict) -> int:
    """EVERY parameter, from the shapes alone: the layers' matrices and
    vectors, the tied embedding once, the final norm."""
    z = sizes(m)
    return (sum(layer_parameters(k, z) for k in z["kinds"])
            + z["v"] * z["d"] + z["d"])


def matrix_weights(m: dict) -> int:
    """The layers' parameters a row is multiplied by (no embedding)."""
    z = sizes(m)
    return sum(layer_parameters(k, z, MATRICES) for k in z["kinds"])


def attention_pairs_flops(pairs: int, z: dict) -> int:
    """``pairs`` live (query, key) pairs of one layer, all heads: QK' over hd
    and PV over hd, 2 flops a product."""
    return pairs * z["h"] * 4 * z["hd"]


def scan_row_flops(z: dict) -> int:
    """One row of one Mamba layer outside its matmuls: the conv, and per
    (channel, state) element exp, decay, drive, sum and the C contraction."""
    return z["di"] * (2 * z["kc"] + 7 * z["n"])


def prefill_chunk_flops(n: int, start: int, m: dict, head: bool) -> int:
    """The MATMUL operations of rows ``[start, start + n)`` of a prompt:
    every projection and MLP over n rows, each attention layer's rows over
    the keys before and among them, the head (one row) where the chunk ends
    its prompt. The scan's elementwise work is not a matmul and not
    counted."""
    z = sizes(m)
    pairs = n * start + n * (n + 1) // 2
    return (2 * n * matrix_weights(m)
            + z["kinds"].count("full") * attention_pairs_flops(pairs, z)
            + (2 * z["d"] * z["v"] if head else 0))


def prefill_flops(s: int, m: dict) -> int:
    """One prompt of s rows, however it is cut into launches: the matmuls
    and the scans' elementwise work."""
    z = sizes(m)
    return (prefill_chunk_flops(s, 0, m, True)
            + s * z["kinds"].count("mamba") * scan_row_flops(z))


def state_bytes_row(z: dict) -> int:
    """The recurrent state of one sequence: every Mamba layer's scan state
    and conv tail, float32."""
    return F32 * z["kinds"].count("mamba") * z["di"] * (z["n"] + z["kc"] - 1)


def prefill_bytes(s: int, m: dict) -> int:
    """Weights read once; written: the recurrent state and the attention
    layers' rows."""
    z = sizes(m)
    rows = 2 * z["kvd"] * z["kinds"].count("full") * s
    return BF16 * (weight_count(m) + rows) + state_bytes_row(z)


def decode_step_flops(contexts, m: dict) -> int:
    """One decode step over a batch: every matrix and the head times each
    row, the scans' one step, one query a row over the whole live context
    of each attention layer."""
    z = sizes(m)
    b = len(contexts)
    pairs = z["kinds"].count("full") * sum(int(c) for c in contexts)
    return (2 * b * (matrix_weights(m) + z["d"] * z["v"])
            + b * z["kinds"].count("mamba") * scan_row_flops(z)
            + attention_pairs_flops(pairs, z))


def decode_step_bytes(contexts, m: dict) -> int:
    """The least any implementation moves: every weight once in bfloat16
    (the tied embedding is the head), each live row's recurrent state and
    conv tail read and written in float32, the live K/V rows of the
    attention layers. Pads of a gathered context are not counted."""
    z = sizes(m)
    rows = z["kinds"].count("full") * sum(int(c) for c in contexts)
    return (BF16 * (weight_count(m) + 2 * z["kvd"] * rows)
            + 2 * len(contexts) * state_bytes_row(z))


# no kernel of this block's own: the chunk's attention calls the flash carry
# kernel the training ring's ``ring_attn_roofline`` reads, and the cell
# lists itself under no kernel metric
KERNELS = {}
