"""The routed expert product's share of its roofline: for each launch of the
traced window and each layer, the larger of its token-expert pairs'
operations (6 x d x ff a pair) over the bf16 peak and of the weights of the
held experts the launch is EXPECTED to hit under even routing, once each,
plus each pair's row in and out, over the HBM peak; summed, over the device
time of the grouped-matmul kernel's events (``tpu/pallas_ops.py:
moe_grouped_matmul``, which serves prefill and decode alike). At decode
batches the bytes bound it. The pattern and the least time are the block's
(``KERNELS`` under this metric's name); ``experts_hit_share`` is the check on
the expectation. Source: device_trace."""

from harness import readers


def read(run):
    return readers.kernel_roofline(run, "moe_expert_roofline")
