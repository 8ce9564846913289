"""``broken_run.py`` for the ``sambay`` block: drive a whole run of ``run.py``
(rehearsal: the look for a chip is skipped, everything else is the run's own
code) with the timed path broken underneath by one fault.
``test_sambay_block.py`` starts this in a process of its own and reads
``correct`` from the line.

    python broken_sambay.py <fault> <workload> [run.py arguments]

Faults: ``none``; ``state_unchanged`` (the recurrent state a launch returns is
dropped: the running state stays what prefill left at the prompt's end);
``window_short`` (the decode steps read a window one row short);
``memory_wrong_layer`` (the gated memory units read the FIRST Mamba layer's
scan output, not layer N/2's); ``shared_kv_float8`` (the ONE full layer's K/V
pool, which every cross layer reads, keeps its rows in float8: tokens stay
plausible, the rows do not).
"""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(1, os.path.dirname(os.path.dirname(HERE)))


def plant(fault: str) -> None:
    if fault == "none":
        return
    from brpc_tpu.serving import hybrid_cache, hybrid_model

    if fault == "state_unchanged":
        orig = hybrid_cache.HybridStateCache.update_state

        def update_state(self, ssm, conv):
            orig(self, ssm.at[0].set(ssm[1]), conv.at[0].set(conv[1]))

        hybrid_cache.HybridStateCache.update_state = update_state
    elif fault == "window_short":
        orig_live = hybrid_model._ring_live

        def ring_live(pos, ring_rows, window):
            return orig_live(pos, ring_rows, window - 1)

        hybrid_model._ring_live = ring_live
    elif fault == "memory_wrong_layer":
        hybrid_model.SambaYConfig.memory_layer = property(lambda self: 0)
    elif fault == "shared_kv_float8":
        init = hybrid_cache.HybridStateCache.__init__

        def __init__(self, *args, **kwargs):
            import jax.numpy as jnp

            init(self, *args, **kwargs)
            install = self.full.update_pools

            def update_pools(k, v):
                f8 = jnp.float8_e4m3fn
                install(k.astype(f8).astype(k.dtype),
                        v.astype(f8).astype(v.dtype))

            self.full.update_pools = update_pools

        hybrid_cache.HybridStateCache.__init__ = __init__
    else:
        raise SystemExit(f"unknown fault {fault!r}")


if __name__ == "__main__":
    fault, workload, rest = sys.argv[1], sys.argv[2], sys.argv[3:]
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    plant(fault)
    import run

    sys.exit(run.main(["--workload", workload, "--rehearse-cpu", "1"] + rest))
