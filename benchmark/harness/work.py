"""Operations and bytes a call needs, from its shapes alone.

The counts are of the algorithm, not of an implementation: whatever kernel
or fusion does the work, the same shapes give the same numbers, so a later
PR cannot raise a roofline share by changing how the work is done. Padding,
recomputation and gathered-but-masked context are NOT counted; a program that
does them reads a lower share, which is the point.

``serve`` dictionaries are the ``runner_args.model`` group of a serve
configuration (vocab, d_model, n_heads, n_layers; the MLP is 2*d_model wide in
``serving/model.py``); ``train`` dictionaries are that of a train
configuration (vocab, d_model, n_layers, d_ff).
"""

F32 = 4


def causal_attention_flops(s: int, d_model: int) -> int:
    """QK^T and PV over the s(s+1)/2 live (query, key) pairs of one causal
    sequence, all heads together: 2 matmuls x 2 flops x d_model per pair."""
    return 2 * d_model * s * (s + 1)


# ------------------------------------------------------------------ serving
def serve_layer_weights(m: dict) -> int:
    d = m["d_model"]
    return d * 3 * d + d * d + 2 * d * m.get("d_mlp", 2 * d)


def serve_weight_count(m: dict) -> int:
    return m["n_layers"] * serve_layer_weights(m) + m["vocab"] * m["d_model"]


def prefill_flops(s: int, m: dict) -> int:
    """One prompt of s tokens: every layer's matmuls over s rows, causal
    attention, and the tied head for the LAST row only (that is all
    ``prefill`` computes)."""
    d = m["d_model"]
    return (m["n_layers"] * (2 * s * serve_layer_weights(m)
                             + causal_attention_flops(s, d))
            + 2 * d * m["vocab"])


def prefill_bytes(s: int, m: dict) -> int:
    """Weights read once, K/V rows of the prompt written once."""
    d = m["d_model"]
    return F32 * (serve_weight_count(m) + m["n_layers"] * 2 * s * d)


def decode_step_flops(contexts, m: dict) -> int:
    """One decode step over a batch: every weight times each row, the head
    for each row, attention of one query over each row's live context."""
    d, b = m["d_model"], len(contexts)
    attn = sum(4 * d * int(c) for c in contexts)
    return (m["n_layers"] * (2 * b * serve_layer_weights(m) + attn)
            + 2 * b * d * m["vocab"])


def decode_step_bytes(contexts, m: dict) -> int:
    """Every weight read once (the tied embedding is the head), plus the
    live K and V rows of each sequence's context in every layer. Pads of
    the gathered context are not counted."""
    d = m["d_model"]
    kv = sum(2 * int(c) * d for c in contexts) * m["n_layers"]
    return F32 * (serve_weight_count(m) + kv)


# ----------------------------------------------------------------- training
def train_matmul_weights(m: dict) -> int:
    """Non-embedding parameters that a token is multiplied by: the layers
    and the untied head. The embedding is a gather."""
    d = m["d_model"]
    layer = d * 3 * d + d * d + 2 * d * m["d_ff"]
    return m["n_layers"] * layer + d * m["vocab"]


def train_attention_flops(seq: int, m: dict) -> int:
    """Causal attention forward + backward for ONE sequence: the backward
    is twice the forward; the recomputation inside a flash backward is not
    counted."""
    return 3 * m["n_layers"] * causal_attention_flops(seq, m["d_model"])


def train_step_flops(batch: int, seq: int, m: dict) -> int:
    """6 x matmul parameters per token, plus attention forward+backward."""
    return batch * (6 * seq * train_matmul_weights(m)
                    + train_attention_flops(seq, m))
