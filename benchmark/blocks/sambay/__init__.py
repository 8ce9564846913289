"""Block ``sambay``: the SambaY decoder-hybrid-decoder (Mamba-1, sliding-window
and full differential attention, gated memory units over one shared K/V;
arXiv:2507.06607), ``serving/hybrid_model.py``'s ``SambaYModel`` over a
``HybridStateCache``: ``standup`` (the program's side), ``reference`` (the
plain forward and its control; imports nothing of the program) and ``work``
(operations and bytes).

What the timed path is held to beyond its tokens, read back after the window
from what the manager still holds of finished sequences (``retired``): the
FIRST Mamba layer's scan state and conv tail at the prompt's end and after
the last decode step, the first window layer's K and V rows still in the
ring, and the K and V rows of the ONE full layer that every cross layer
reads. The first two lie a few matmuls deep, where a reference that rounds
the same operands reproduces them to rounding; the full layer's rows lie ten
layers deep, where the program stands at a level of its own (two bfloat16-
operand computations that have parted round independently from there on) and
the bfloat16-storage control 2.4 to 3.5 times above it: its limits lie
between the two (PERF.md section 4 has every reading). ``kv_gap_by_layer``
prints all six places.
"""

from .reference import HostWeights, Reference
from .reference import NOTHING, PARTS, state_gaps              # noqa: F401
from .standup import (build, describe, held_state, release,    # noqa: F401
                      vocab, warm_programs)
from .work import (KERNELS, decode_step_bytes,                 # noqa: F401
                   decode_step_flops, prefill_bytes, prefill_flops)

STATE_CHECKS = {"ssm0_gap_prefill": "prefill", "ssm0_gap_decode": "decode",
                "kv1_gap_prefill": "prefill", "kv1_gap_decode": "decode",
                "kvf_gap_prefill": "prefill", "kvf_gap_decode": "decode"}
STATE_SHORT = "state_short"
_PLACES = {"ssm0": ("ssm", "conv"), "kv1": ("k1", "v1"),
           "kvf": ("kf", "vf")}


def host_weights(seed: int, args: dict) -> HostWeights:
    return HostWeights(seed, args["model"])


def reference(seed: int, args: dict, host_weights=None,
              pad_to: int = 512) -> Reference:
    return Reference(seed, args["model"], args["reference"]["mode"],
                     host_weights=host_weights, pad_to=pad_to)


def compared(name: str, gaps) -> float:
    """The farther of the two arrays the name covers (scan state and conv
    tail; K and V). A part in which NO sampled request had anything to read
    is not correct: a number past every limit."""
    worst = max(float(gaps[PARTS.index(k)])
                for k in _PLACES[name.split("_")[0]])
    return 1e30 if worst == NOTHING else worst
