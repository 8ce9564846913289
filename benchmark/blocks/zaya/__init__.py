"""Block ``zaya``: the ``zaya`` decoder (ZAYA1-8B: every layer a compressed
convolutional attention of eight query heads over two key/value heads inside
a narrow latent, queries and keys mixed over time by two short convs, the
second value head taken from the token before; then one of 16 experts, or
none, chosen by an MLP router whose state runs down the layers; learned
residual scaling; RMSNorm; tied head), ``serving/zaya_model.py``'s
``ZayaModel`` over a ``HybridStateCache`` with pages of every layer beside a
conv tail a layer and no scan state: ``standup`` (the program's side),
``reference`` (the plain forward and its control; imports nothing of the
program) and ``work`` (operations and bytes).

What the timed path is held to beyond its tokens, read back after the window
from what the manager still holds of finished sequences (``retired``): layer
0's K and V rows and its tail at the prompt's end and after the last decode
step (``kv0_gap_*``, ``tail0_gap_*``: they depend on no routing and are
compared whole; the V rows' second head and the tail are where a wrong shift
or a tail dropped at a chunk boundary shows), and the LAST layer's K and V
rows (``kvL_gap_*``: every expert sublayer and every hand-down of the
router's state before them), as the MEDIAN of the rows' own distances over
the positions whose routing is not thin: two correct computations in
bfloat16 part by rounding, and a top-1 router turns that into another expert
for a share of the rows that grows with depth (``reference.py`` has the
mechanism and the rule). A token whose largest and second ``p + bias`` lie
within ``route_margin`` of each other (scaled by depth) in any layer is THIN:
it is left out of the last layer's rows and (at twice the margin, with the
tenth of a request's served rows that lie farthest off) of ``logit_gap``, and
the share of such tokens among a request's prompt rows is itself compared
(``route_thin_share_prefill``). ``kv_gap_by_layer`` prints all six places.
"""

from .reference import HostWeights, Reference
from .reference import NOTHING, PARTS, state_gaps              # noqa: F401
from .standup import (build, describe, held_state, release,    # noqa: F401
                      vocab, warm_programs)
from .work import (KERNELS, decode_step_bytes,                 # noqa: F401
                   decode_step_flops, prefill_bytes, prefill_chunk_flops,
                   prefill_flops, weight_count)

STATE_CHECKS = {"kv0_gap_prefill": "prefill", "kv0_gap_decode": "decode",
                "tail0_gap_prefill": "prefill", "tail0_gap_decode": "decode",
                "kvL_gap_prefill": "prefill", "kvL_gap_decode": "decode",
                "route_thin_share_prefill": "prefill"}
STATE_SHORT = "state_short"
_PLACES = {"kv0": ("k0", "v0"), "tail0": ("tail0",), "kvL": ("kL", "vL"),
           "route": ("thin",)}


def host_weights(seed: int, args: dict) -> HostWeights:
    return HostWeights(seed, args["model"])


def reference(seed: int, args: dict, host_weights=None,
              pad_to: int = 512) -> Reference:
    return Reference(seed, args["model"], args["reference"]["mode"],
                     host_weights=host_weights, pad_to=pad_to,
                     route_margin=args["reference"].get("route_margin", 0.0))


def compared(name: str, gaps) -> float:
    """The farther of the arrays the name covers (K and V; the tail or the
    thin share alone). A part in which NO sampled request had anything to
    read is not correct: a number past every limit."""
    worst = max(float(gaps[PARTS.index(k)])
                for k in _PLACES[name.split("_")[0]])
    return 1e30 if worst == NOTHING else worst
