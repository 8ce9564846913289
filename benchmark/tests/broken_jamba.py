"""``broken_run.py`` for the ``jamba`` block: drive a whole run of ``run.py``
(rehearsal: the look for a chip is skipped, everything else is the run's own
code) with the timed path broken underneath by one fault.
``test_jamba_block.py`` starts this in a process of its own and reads
``correct`` from the line.

    python broken_jamba.py <fault> <workload> [run.py arguments]

Faults: ``none``; ``scan_from_zero`` (a later chunk's scan starts from zero,
not from the slot's state); ``conv_tail_dropped`` (a later chunk's conv is fed
zeros, not the slot's tail); ``no_inner_norms`` (``dt``, B and C go on without
their RMS norms); ``attention_wrong_index`` (the attention layers lie one
layer late in each period); ``k_rows_for_v`` (the pages' V rows are the K
rows).
"""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(1, os.path.dirname(os.path.dirname(HERE)))


def plant(fault: str) -> None:
    if fault == "none":
        return
    from brpc_tpu.serving import hybrid_cache, hybrid_model, jamba_model

    if fault == "scan_from_zero":
        orig_scan = jamba_model.ssm_scan
        jamba_model.ssm_scan = \
            lambda dt, u, bm, cm, a, s0=None: orig_scan(dt, u, bm, cm, a)
    elif fault == "conv_tail_dropped":
        orig_windows = jamba_model.conv_windows
        jamba_model.conv_windows = \
            lambda u_in, tail: orig_windows(u_in, tail * 0.0)
    elif fault == "no_inner_norms":
        orig_rms = hybrid_model._rms
        inner = set()

        def rms(x, w, eps):
            return x * w if x.shape[-1] in inner else orig_rms(x, w, eps)

        init = jamba_model.JambaConfig.__init__

        def __init__(self, *args, **kwargs):
            init(self, *args, **kwargs)
            inner.update((self.dt_rank, self.d_state))

        jamba_model.JambaConfig.__init__ = __init__
        hybrid_model._rms = rms
    elif fault == "attention_wrong_index":
        init = jamba_model.JambaConfig.__init__

        def __init__(self, *args, **kwargs):
            init(self, *args, **kwargs)
            self.kinds = self.kinds[-1:] + self.kinds[:-1]

        jamba_model.JambaConfig.__init__ = __init__
    elif fault == "k_rows_for_v":
        init = hybrid_cache.HybridStateCache.__init__

        def __init__(self, *args, **kwargs):
            init(self, *args, **kwargs)
            import jax.numpy as jnp

            install = self.full.update_pools
            self.full.update_pools = \
                lambda k, v: install(k, jnp.array(k, copy=True))

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
