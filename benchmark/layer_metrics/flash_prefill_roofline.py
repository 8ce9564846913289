"""The flash-forward kernel's share of its roofline in served prefill: for
each prompt of the traced window and each layer, the larger of the causal
attention operations over the bf16 peak and of q, k, v read and o written
once (float32) over the HBM peak; summed, over the device time of the
kernel's events (``tpu/pallas_ops.py:_flash_kernel``). At these lengths and
a head size of 128 in float32 the bytes bound it. Source: device_trace."""

from harness.readers import flash_prefill_roofline as read  # noqa: E402,F401
