"""The ring's attention kernels' share of their roofline: causal attention
forward + backward operations of the traced steps at the cell's sequence
length (``work.train_attention_flops``), over the bf16 peak, over the device
time of the ring's kernel events (the carry-form flash forward and the dq and
dkv backward kernels of ``tpu/pallas_ops.py``) summed over the devices. Held
to the FLOPs peak (bf16, head size 128, thousands of keys a block).
Source: device_trace."""

from harness import work

KERNELS = r"_flash_carry_kernel|_flash_dq_kernel|_flash_dkv_kernel|flash"


def read(run):
    red, tr = run.reduced, getattr(run, "train", None)
    if red is None or run.peak is None or tr is None:
        return None
    ns = sum(red.op_ns(KERNELS, dev)[0] for dev in red.devices)
    if not ns:
        return None
    flops = run.traced_steps * tr["batch"] * work.train_attention_flops(
        tr["seq"], tr["model"])
    return 100.0 * flops / run.peak["bf16_flops"] / (ns / 1e9)
