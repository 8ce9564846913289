"""The absorbed decode attention's share of its roofline: for each decode
launch of the traced window and each layer, the larger of its live latent
rows' bytes, each read ONCE as key and value (``2 x (kv_lora_rank +
qk_rope_head_dim)`` in bfloat16), over the HBM peak and of ``2 x heads x
(row + kv_lora_rank)`` operations a row over the bf16 peak (the bytes, at 20
heads); summed, over the device time of the decode kernel's events
(``tpu/pallas_ops.py:mla_paged_decode``). The call log holds every decode
row's context length; pattern and least time are the block's (``KERNELS``
under this metric's name). Nothing where the block has no such kernel or the
trace holds no such op. Source: device_trace."""

from harness import readers


def read(run):
    return readers.kernel_roofline(run, "mla_decode_roofline")
