"""``broken_run.py`` for the ``zaya`` block: drive a whole run of ``run.py``
(rehearsal: the look for a chip is skipped, everything else is the run's own
code) with the timed path broken underneath by one fault.
``test_zaya_block.py`` starts this in a process of its own and reads
``correct`` from the line.

    python broken_zaya.py <fault> <workload> [run.py arguments]

Faults: ``none``; ``tail_dropped`` (a later chunk's convs are fed zeros, not
the slot's tail); ``value_same_token`` (the second value head is taken from
the same token, not from the one before); ``router_state_not_handed_down``
(every layer's router starts from zero, not from the state of the layer
below); ``skip_as_expert_0`` (a row the router sends to the output that
computes nothing is given expert 0); ``k_before_rotary`` (the pages' K rows
are stored, and read, unrotated).
"""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(1, os.path.dirname(os.path.dirname(HERE)))


def plant(fault: str) -> None:
    if fault == "none":
        return
    from brpc_tpu.serving import zaya_model

    if fault == "tail_dropped":
        orig_windows = zaya_model.conv_windows
        zaya_model.conv_windows = \
            lambda u_in, tail: orig_windows(u_in, tail * 0.0)
    elif fault == "value_same_token":
        orig_layer = zaya_model.ZayaModel._layer

        def _layer(self, w, i, x, r, counts, live, pos, tile, mix, attend):
            def same_token(zz, v2):
                return mix(zz, v2)[0], v2

            return orig_layer(self, w, i, x, r, counts, live, pos, tile,
                              same_token, attend)

        zaya_model.ZayaModel._layer = _layer
    elif fault == "router_state_not_handed_down":
        orig_router = zaya_model.zaya_router
        zaya_model.zaya_router = \
            lambda cfg, wl, h, r, live: orig_router(cfg, wl, h, r * 0.0, live)
    elif fault == "skip_as_expert_0":
        orig_router = zaya_model.zaya_router

        def zaya_router(cfg, wl, h, r, live):
            import jax.numpy as jnp

            r, idx, p_e = orig_router(cfg, wl, h, r, live)
            return r, jnp.where(idx == cfg.n_experts, 0, idx), p_e

        zaya_model.zaya_router = zaya_router
    elif fault == "k_before_rotary":
        orig_rope = zaya_model.rope_half
        # the keys come (rows, 2 key/value heads, hd); the queries have more
        zaya_model.rope_half = lambda x, pos, rot, theta: (
            x if x.shape[1] == 2 else orig_rope(x, pos, rot, theta))
    else:
        raise SystemExit(f"unknown fault {fault!r}")


if __name__ == "__main__":
    fault, workload, rest = sys.argv[1], sys.argv[2], sys.argv[3:]
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    plant(fault)
    import run

    sys.exit(run.main(["--workload", workload, "--rehearse-cpu", "1"] + rest))
