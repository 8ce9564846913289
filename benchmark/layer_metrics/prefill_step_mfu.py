"""The whole prefill step's share of the chip's peak: the matmul and causal
attention operations of the traced window's prompts (``work.prefill_flops``,
from their true lengths, pads not counted) over the device time inside
``bench.prefill`` annotations, over the bf16 peak. Held to the FLOPs peak.
Source: device_trace (time) and the call log (prompt lengths)."""

from harness.readers import prefill_step_mfu as read  # noqa: E402,F401
