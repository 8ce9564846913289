"""``broken_run.py`` for the ``cohere2moe`` block: drive a whole run of
``run.py`` (rehearsal: the look for a chip is skipped, everything else is the
run's own code) with the timed path broken underneath by one fault.
``test_cohere2moe_block.py`` starts this in a process of its own and reads
``correct`` from the line.

    python broken_cohere2moe.py <fault> <workload> [run.py arguments]

Faults: ``none``; ``expert_float8`` (the held experts' weights are read as
float8 e4m3 holds them, no scale: tokens stay plausible, the layers after
them do not);
``router_top7`` (the router keeps one expert fewer a token and renormalises
over those); ``shared_sum`` (the shared experts' outputs are summed, not
averaged).
"""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(1, os.path.dirname(os.path.dirname(HERE)))


def plant(fault: str) -> None:
    if fault == "none":
        return
    import copy

    from brpc_tpu.serving import moe_model

    if fault == "expert_float8":
        orig = moe_model.expert_layer

        def expert_layer(cfg, h, idx, wts, wgu, wd, tile):
            import jax.numpy as jnp

            def f8(w):
                return w.astype(jnp.float8_e4m3fn).astype(w.dtype)

            return orig(cfg, h, idx, wts, f8(wgu), f8(wd), tile)

        moe_model.expert_layer = expert_layer
    elif fault == "router_top7":
        orig_route = moe_model.route

        def route(cfg, h, w_router, live):
            import jax.numpy as jnp

            fewer = copy.copy(cfg)
            fewer.top_k = cfg.top_k - 1
            idx, wts = orig_route(fewer, h, w_router, live)
            return (jnp.concatenate([idx, -jnp.ones_like(idx[:, :1])], 1),
                    jnp.concatenate([wts, jnp.zeros_like(wts[:, :1])], 1))

        moe_model.route = route
    elif fault == "shared_sum":
        orig_shared = moe_model.shared_experts

        def shared_experts(cfg, h, wgu, wd):
            return orig_shared(cfg, h, wgu, wd) * cfg.n_shared

        moe_model.shared_experts = shared_experts
    else:
        raise SystemExit(f"unknown fault {fault!r}")


if __name__ == "__main__":
    fault, workload, rest = sys.argv[1], sys.argv[2], sys.argv[3:]
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    plant(fault)
    import run

    sys.exit(run.main(["--workload", workload, "--rehearse-cpu", "1"] + rest))
