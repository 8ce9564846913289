"""Block ``jamba``: the ``jamba`` decoder (AI21-Jamba2-3B: Mamba-1 layers whose
``dt``, B and C pass an RMS norm each, beside position-free attention layers
of twenty query heads over ONE key/value head; gated SiLU MLPs; RMSNorm; tied
head), ``serving/jamba_model.py``'s ``JambaModel`` over a
``HybridStateCache`` with recurrent slots, two full layers' pages and no
ring: ``standup`` (the program's side), ``reference`` (the plain forward and
its control; imports nothing of the program) and ``work`` (operations and
bytes).

What the timed path is held to beyond its tokens, read back after the window
from what the manager still holds of finished sequences (``retired``): the
FIRST Mamba layer's scan state and conv tail at the prompt's end and after
the last decode step (``ssm0_gap_*``: one matmul chain deep, and at the
prompt's end it has crossed every chunk boundary of the prompt), the LAST
Mamba layer's (``ssmL``: the whole depth; printed in ``kv_gap_by_layer``,
and see ``STATE_CHECKS``), and the first attention layer's K and V rows in
the pages (``kvf_gap_*``, under every Mamba layer before it). PERF.md section 4 has
every reading.
"""

from .reference import HostWeights, Reference
from .reference import NOTHING, PARTS, state_gaps              # noqa: F401
from .standup import (build, describe, held_state, release,    # noqa: F401
                      vocab, warm_programs)
from .work import (KERNELS, decode_step_bytes,                 # noqa: F401
                   decode_step_flops, prefill_bytes, prefill_chunk_flops,
                   prefill_flops, weight_count)

STATE_CHECKS = {"ssm0_gap_prefill": "prefill", "ssm0_gap_decode": "decode",
                "ssmL_gap_prefill": "prefill", "ssmL_gap_decode": "decode",
                "kvf_gap_prefill": "prefill", "kvf_gap_decode": "decode"}
STATE_SHORT = "state_short"
_PLACES = {"ssm0": ("ssm0", "conv0"), "ssmL": ("ssmL", "convL"),
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
