"""Block ``glm4moelite``: the ``glm4_moe_lite`` decoder (GLM-4.7-Flash:
multi-head latent attention whose cache holds ONE row a token a layer, the
compressed latent beside one shared rotary key; a leading dense layer, then
expert layers of sigmoid-routed top-4 of 64 experts chosen with a stored
selection bias, beside one shared expert; RMSNorm; untied head),
``serving/glm_model.py``'s ``GlmMoeLiteModel`` over a ``HybridStateCache``
whose pages are one array (``v_dim = 0``), as ONE chip's share of an 8-way
expert-parallel layer: ``standup`` (the program's side), ``reference`` (the
plain forward in the EXPANDED form and its control; imports nothing of the
program) and ``work`` (operations and bytes).

What the timed path is held to beyond its tokens, read back after the window
from what the manager still holds of finished sequences (``retired``): layer
0's latent rows (``lat0_gap_*``: they depend on no routing and are compared
whole, to the rounding of single elements: the down-projection, its norm, the
rotary key and where a chunk or a decode step put the row) and the LAST
layer's latent rows (``latL_gap_*``: every attention sublayer before them,
prefill's expanded and decode's absorbed alike, every expert sublayer and the
dense layer), as the MEDIAN of the rows' own distances over the positions
whose routing is not thin (``reference.py`` has the mechanism and the rule).
A token that has an expert held here within ``route_margin`` of the boundary
between its 4th and 5th ``s + b`` (scaled by depth) in any layer is THIN: it
is left out of the last layer's rows and (at twice the margin, with the tenth
of a request's served rows that lie farthest off) of ``logit_gap``, and the
share of such tokens among a request's prompt rows is itself compared
(``route_thin_share_prefill``). Every comparison of a decode step with the
reference is also the proof that the absorbed form equals the expanded one.
``kv_gap_by_layer`` prints all three places.
"""

from .reference import HostWeights, Reference
from .reference import NOTHING, PARTS, state_gaps              # noqa: F401
from .standup import (build, describe, held_state, release,    # noqa: F401
                      vocab, warm_programs)
from .work import (KERNELS, decode_step_bytes,                 # noqa: F401
                   decode_step_flops, mla_decode_least_s,
                   moe_expert_least_s, prefill_bytes, prefill_chunk_flops,
                   prefill_flops, weight_count)

STATE_CHECKS = {"lat0_gap_prefill": "prefill", "lat0_gap_decode": "decode",
                "latL_gap_prefill": "prefill", "latL_gap_decode": "decode",
                "route_thin_share_prefill": "prefill"}
STATE_SHORT = "state_short"
_PLACES = {"lat0": "lat0", "latL": "latL", "route": "thin"}


def host_weights(seed: int, args: dict) -> HostWeights:
    return HostWeights(seed, args["model"])


def reference(seed: int, args: dict, host_weights=None,
              pad_to: int = 512) -> Reference:
    return Reference(seed, args["model"], args["reference"]["mode"],
                     host_weights=host_weights, pad_to=pad_to,
                     route_margin=args["reference"].get("route_margin", 0.0))


def compared(name: str, gaps) -> float:
    """The part the name covers. A part in which NO sampled request had
    anything to read is not correct: a number past every limit."""
    worst = float(gaps[PARTS.index(_PLACES[name.split("_")[0]])])
    return 1e30 if worst == NOTHING else worst
