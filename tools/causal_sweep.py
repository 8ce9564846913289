"""Causal flash block/bn sweep on the real chip (round-5, VERDICT r4 #1).

Measures the flash forward at the flagship shape (B=4 H=8 S=2048 D=128)
across (block_q, block_k, bn, causal) configs. Causal rows report % of
v5e bf16 peak with the CAUSAL flop count (lower-triangular useful MACs).

Methodology: a single pass per config is useless for A/B decisions when
the machine drifts between passes (how much the current machine drifts is
not measured). This sweep interleaves: every config's marginal slope
is measured once per OUTER pass, 3 passes round-robin over the whole
config list, and the reported number is the MEDIAN of the 3 passes (all
within one process, compile cache warm after pass 1).
"""

import functools
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

V5E_PEAK_FLOPS = 197e12


def _marginal_once(fn, lo, hi, reps=2):
    tls, this = [], []
    for _ in range(reps):
        t0 = time.perf_counter()
        fn(lo)
        tls.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        fn(hi)
        this.append(time.perf_counter() - t0)
    return max((min(this) - min(tls)) / (hi - lo), 1e-12)


def main():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from brpc_tpu.tpu.pallas_ops import _flash_fwd_bhsd

    B, H, S, D = 4, 8, 2048, 128
    N = B * H
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(N, S, D)), dtype=jnp.bfloat16)
    k = jnp.asarray(rng.normal(size=(N, S, D)), dtype=jnp.bfloat16)
    v = jnp.asarray(rng.normal(size=(N, S, D)), dtype=jnp.bfloat16)

    causal_flops = 2.0 * B * H * S * (S + 1) * D
    full_flops = 4.0 * B * H * S * S * D

    # (causal, bq, bk, bn)
    cfgs = [
        (False, 512, 2048, 1),   # the r4 shipping default (sentinel)
        (False, 512, 2048, 2),
        (False, 512, 2048, 4),
        (False, 1024, 1024, 1),  # drift probe
        (True, 1024, 1024, 1),
        (True, 1024, 1024, 2),
        (True, 1024, 1024, 4),
        (True, 512, 1024, 1),
        (True, 512, 1024, 2),
        (True, 512, 1024, 4),
        (True, 512, 512, 2),
        (True, 512, 512, 4),
        (True, 256, 512, 4),
        (True, 256, 512, 8),
        (True, 256, 256, 4),
        (True, 256, 256, 8),
        (True, 128, 128, 8),
    ]

    runners = {}
    for cfg in cfgs:
        causal, bq, bk, bn = cfg

        @functools.partial(jax.jit, static_argnames=("n",))
        def loop(q, k, v, n: int, bq=bq, bk=bk, bn=bn, causal=causal):
            def body(i, acc):
                q2 = q.at[0, 0, 0].add(acc.astype(q.dtype))
                o, _ = _flash_fwd_bhsd(q2, k, v, causal, bq, bk, False, bn)
                return acc + o[0, 0, 0].astype(jnp.float32) * 1e-6

            return jax.lax.fori_loop(0, n, body, jnp.float32(0))

        def run(n, loop=loop):
            float(jax.device_get(loop(q, k, v, n)))

        runners[cfg] = run

    # compile everything first (one warm call per count)
    ok = {}
    for cfg, run in runners.items():
        try:
            run(64)
            run(512)
            ok[cfg] = run
        except Exception as e:
            print(f"cfg={cfg}: FAIL {type(e).__name__}: {e}", flush=True)

    secs = {cfg: [] for cfg in ok}
    for p in range(3):
        for cfg, run in ok.items():
            secs[cfg].append(_marginal_once(run, 64, 512))
        print(f"# pass {p} done", flush=True)

    for cfg in ok:
        causal, bq, bk, bn = cfg
        med = statistics.median(secs[cfg])
        best = min(secs[cfg])
        flops = causal_flops if causal else full_flops
        tfm = flops / med / 1e12
        tfb = flops / best / 1e12
        print(f"causal={int(causal)} bq={bq:5d} bk={bk:5d} bn={bn:2d}: "
              f"median {tfm:7.2f} TF/s ({tfm*1e12/V5E_PEAK_FLOPS*100:5.1f}%)"
              f"  best {tfb:7.2f} ({tfb*1e12/V5E_PEAK_FLOPS*100:5.1f}%)",
              flush=True)


if __name__ == "__main__":
    main()
