"""Block ``cohere2moe``: the ``cohere2_moe`` decoder (Command A+: a parallel
attention + FFN block, rotary window layers beside position-free full
layers, 128 query over 8 key/value heads, sigmoid-routed top-8 experts beside
4 averaged shared experts), ``serving/moe_model.py``'s ``Cohere2MoeModel``
over a ``HybridStateCache``, as ONE chip's share of an expert-parallel layer:
``standup`` (the program's side), ``reference`` (the plain forward and its
control; imports nothing of the program) and ``work`` (operations and bytes).

What the timed path is held to beyond its tokens, read back after the window
from what the manager still holds of finished sequences (``retired``): the
FIRST window layer's K and V rows still in the ring (K rotated; they depend
on no routing, and a reference that stores the same bfloat16 values
reproduces them to the rounding of a few elements), and the first FULL
layer's rows at positions whose routing is not thin. A token that has an
expert held here within ``route_margin`` of the boundary between its 8th and
9th router scores is THIN (``reference.py``): it is left out of
``logit_gap`` and of the full layer's rows, and the share of such tokens
among a request's prompt rows is itself compared
(``route_thin_share_prefill``). ``kv_gap_by_layer`` prints all five places,
the served rows' thin share among them.
"""

from .reference import HostWeights, Reference
from .reference import NOTHING, PARTS, state_gaps              # noqa: F401
from .standup import (build, describe, held_state, release,    # noqa: F401
                      vocab, warm_programs)
from .work import (KERNELS, decode_step_bytes,                 # noqa: F401
                   decode_step_flops, prefill_bytes, prefill_flops)

STATE_CHECKS = {"kv0_gap_prefill": "prefill", "kv0_gap_decode": "decode",
                "kvf_gap_prefill": "prefill", "kvf_gap_decode": "decode",
                "route_thin_share_prefill": "prefill"}
STATE_SHORT = "state_short"
_PLACES = {"kv0": ("k0", "v0"), "kvf": ("kf", "vf"), "route": ("thin",)}


def host_weights(seed: int, args: dict) -> HostWeights:
    return HostWeights(seed, args["model"])


def reference(seed: int, args: dict, host_weights=None,
              pad_to: int = 512) -> Reference:
    return Reference(seed, args["model"], args["reference"]["mode"],
                     host_weights=host_weights, pad_to=pad_to,
                     route_margin=args["reference"].get("route_margin", 0.0))


def compared(name: str, gaps) -> float:
    """The farther of the arrays the name covers (K and V; the thin share
    alone). A part in which NO sampled request had anything to read is not
    correct: a number past every limit."""
    worst = max(float(gaps[PARTS.index(k)])
                for k in _PLACES[name.split("_")[0]])
    return 1e30 if worst == NOTHING else worst
