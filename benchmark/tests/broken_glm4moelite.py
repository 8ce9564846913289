"""``broken_run.py`` for the ``glm4moelite`` block: drive a whole run of
``run.py`` (rehearsal: the look for a chip is skipped, everything else is the
run's own code) with the timed path broken underneath by one fault.
``test_glm4moelite_block.py`` starts this in a process of its own and reads
``correct`` from the line.

    python broken_glm4moelite.py <fault> <workload> [run.py arguments]

Faults: ``none``; ``chunk_from_row_0`` (a later chunk of a prompt masks its
context as if its first row were row 0); ``key_before_rotary`` (the pages'
rotary key is stored, and read, unrotated); ``scale_forgotten`` (the routed
weights are not multiplied by the scaling factor); ``decode_reads_one_page``
(the absorbed decode attends to a row's first page alone);
``shared_expert_dropped`` (the shared expert adds nothing).
"""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(1, os.path.dirname(os.path.dirname(HERE)))


def plant(fault: str) -> None:
    if fault == "none":
        return
    from brpc_tpu.serving import glm_model
    from brpc_tpu.tpu import pallas_ops

    if fault == "chunk_from_row_0":
        orig_carry = pallas_ops.flash_attention_carry

        def carry(q, k, v, m, l, acc, q_start, k_start, **kw):
            return orig_carry(q, k, v, m, l, acc, 0, k_start, **kw)

        pallas_ops.flash_attention_carry = carry
    elif fault == "key_before_rotary":
        orig_rope = glm_model.rope
        # the ONE key a token comes (rows, 1, rot); the queries have heads
        glm_model.rope = lambda x, pos, theta: (
            x if x.shape[1] == 1 else orig_rope(x, pos, theta))
    elif fault == "scale_forgotten":
        orig_route = glm_model.route
        glm_model.route = lambda *a, **kw: orig_route(
            *a, **dict(kw, scale=None))
    elif fault == "decode_reads_one_page":
        orig_decode = pallas_ops.mla_paged_decode

        def decode(q, pool, layer, tables, lengths, **kw):
            import jax.numpy as jnp

            return orig_decode(q, pool, layer, tables,
                               jnp.minimum(lengths, kw["block_size"]), **kw)

        pallas_ops.mla_paged_decode = decode
    elif fault == "shared_expert_dropped":
        glm_model.shared_experts = lambda cfg, h, wgu, wd: h * 0.0
    else:
        raise SystemExit(f"unknown fault {fault!r}")


if __name__ == "__main__":
    fault, workload, rest = sys.argv[1], sys.argv[2], sys.argv[3:]
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    plant(fault)
    import run

    sys.exit(run.main(["--workload", workload, "--rehearse-cpu", "1"] + rest))
