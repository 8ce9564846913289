"""Kernel numbers on the real chip for BENCH (VERDICT r3 #2/#3).

Run standalone (owns the chip):

    python tools/kernel_bench.py            # prints one line per metric
    python tools/kernel_bench.py paged_decode   # only the named benches
    python tools/kernel_bench.py ssm_scan       # the prefill's selective scan
    python tools/kernel_bench.py cca_mix        # the zaya lane's decode-side mix
    python tools/kernel_bench.py sambay_decode  # SambaY's decode program, both reads
    python tools/kernel_bench.py mla_paged_decode   # latent pages' decode kernel
    python tools/kernel_bench.py mla_chunk      # a later chunk's latent attention
    python tools/kernel_bench.py ring_hops      # needs four chips

Timing methodology: marginal cost between two round counts inside ONE
compiled loop. Every measurement ends in a dependent fetch, and the slope
between the two counts takes the fixed dispatch + sync cost out. The loop
body CHAINS the op (x_{i+1} = f(x_i)) instead of perturbing one element
of the input (docs/round4-notes.md §3) — an `x.at[0,0].add` anti-hoisting
trick copies the whole input every iteration, which for memory-bound
kernels doubles the true traffic and halves the reported bandwidth.

Every line names the device it ran on; a device_kind that CHIP_PEAKS does
not list is an error, not a default.
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# Published chip peaks keyed by jax's ``device_kind`` — the MFU and
# roofline denominators. Source: Google Cloud documentation, "TPU v5e"
# (197 TFLOP/s bf16, 819 GB/s HBM per chip).
CHIP_PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
}

def chip_peak(device_kind: str) -> dict:
    try:
        return CHIP_PEAKS[device_kind]
    except KeyError:
        raise SystemExit(
            f"kernel bench: no published peak for device_kind="
            f"{device_kind!r}; add it to CHIP_PEAKS with its source") \
            from None


def _pct_of_peak(tflops: float, peak: dict) -> str:
    return f"{tflops * 1e12 / peak['bf16_flops'] * 100:.1f}% of bf16 peak"


def _marginal(fn, lo, hi, reps=4):
    """Seconds per unit via the (hi - lo) slope; min over reps (work at
    `hi` must dwarf the fixed dispatch + sync cost for the slope to be
    stable)."""
    fn(lo)  # compile both
    fn(hi)
    tls, this = [], []
    for _ in range(reps):
        t0 = time.perf_counter()
        fn(lo)
        tls.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        fn(hi)
        this.append(time.perf_counter() - t0)
    return max((min(this) - min(tls)) / (hi - lo), 1e-12)


def bench_flash_attention(peak: dict):
    """Forward + fwd/bwd at the flagship shape, BOTH causal (the shape the
    flagship LM trains — VERDICT r4 #1/#4) and non-causal; causal rows use
    the causal (lower-triangular) flop count. A control row runs the
    public JAX splash-attention kernel on the same shape as a yardstick
    (per-grid-step overhead, docs/round5-notes.md)."""
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np

    from brpc_tpu.tpu.pallas_ops import flash_attention_mha

    B, H, S, D = 4, 8, 2048, 128
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(B, H, S, D)), dtype=jnp.bfloat16)
    k = jnp.asarray(rng.normal(size=(B, H, S, D)), dtype=jnp.bfloat16)
    v = jnp.asarray(rng.normal(size=(B, H, S, D)), dtype=jnp.bfloat16)
    full_fwd_flops = 4.0 * B * H * S * S * D  # QK^T + PV, 2 flops per MAC
    causal_fwd_flops = 2.0 * B * H * S * (S + 1) * D

    for causal in (True, False):
        @functools.partial(jax.jit, static_argnames=("n",))
        def loop(q, k, v, n: int, causal=causal):
            def body(i, acc):
                # acc feeds q so the kernel is NOT loop-invariant; q is
                # tiny (8MB) next to the compute
                q2 = q.at[0, 0, 0, 0].add(acc.astype(q.dtype))
                o = flash_attention_mha(q2, k, v, causal=causal,
                                        interpret=False)
                return acc + o[0, 0, 0, 0].astype(jnp.float32) * 1e-6

            return jax.lax.fori_loop(0, n, body, jnp.float32(0))

        def run(n, loop=loop):
            float(jax.device_get(loop(q, k, v, n)))

        sec = _marginal(run, 64, 512)
        flops = causal_fwd_flops if causal else full_fwd_flops
        tf = flops / sec / 1e12
        tag = "CAUSAL (flagship shape)" if causal else "non-causal"
        print(f"# kernel flash_attention fwd {tag} B={B} H={H} S={S} "
              f"D={D}: {tf:7.2f} TFLOP/s ({_pct_of_peak(tf, peak)})",
              flush=True)

        def f(q, k, v, causal=causal):
            o = flash_attention_mha(q, k, v, causal=causal,
                                    interpret=False)
            return jnp.sum(o.astype(jnp.float32) * 1e-3)

        g = jax.grad(f, argnums=(0, 1, 2))

        @functools.partial(jax.jit, static_argnames=("n",))
        def loop_bwd(q, k, v, n: int, g=g):
            def body(i, acc):
                q2 = q.at[0, 0, 0, 0].add(acc.astype(q.dtype))
                dq, dk, dv = g(q2, k, v)
                return acc + (dq[0, 0, 0, 0] + dk[0, 0, 0, 0]
                              + dv[0, 0, 0, 0]).astype(jnp.float32) * 1e-6

            return jax.lax.fori_loop(0, n, body, jnp.float32(0))

        def run_bwd(n, loop_bwd=loop_bwd):
            float(jax.device_get(loop_bwd(q, k, v, n)))

        sec = _marginal(run_bwd, 32, 256)
        # fwd 2 matmuls + bwd 5 matmuls per (q, k) tile pair
        flops = 3.5 * (causal_fwd_flops if causal else full_fwd_flops)
        tf = flops / sec / 1e12
        print(f"# kernel flash_attention fwd+bwd {tag} "
              f"(custom-vjp Pallas backward): {tf:7.2f} TFLOP/s "
              f"({_pct_of_peak(tf, peak)})", flush=True)
    _bench_splash_control(q, k, v, causal_fwd_flops, peak)
    _bench_grouped_forward(peak)
    _bench_toy_prefill(peak)
    return tf


def _bench_toy_prefill(peak: dict, lengths=(384, 896, 1536)):
    """The toy serving block's prefill attention alone (``tiny-w2048-serve``:
    16 heads of 128, float32, one prompt's packed projection (S, 6144)) at a
    short, a middle and the longest bucket of ``prefill-closed``: the
    rows-first call beside the heads-first call of the same folded
    forward (``flash_attention_mha`` over transposed heads, its copies
    included), and the least time the benchmark's ``flash_prefill_roofline``
    divides by. A call
    writes its output over the q columns of the next call's rows; ``chain``
    is that write alone, already taken off the other two."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from brpc_tpu.tpu import pallas_ops

    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark"))
    from blocks.tiny import work

    H, D = 16, 128
    W = H * D

    def rows_first(x):
        return pallas_ops.flash_attention_rows(
            x, x, x, H, D, heads_at=(0, H, 2 * H), interpret=False)

    def heads_first(x):
        q, k, v = (part.reshape(-1, H, D).transpose(1, 0, 2)[None]
                   for part in jnp.split(x, 3, axis=-1))
        out = pallas_ops.flash_attention_mha(q, k, v, causal=True,
                                             interpret=False)
        return out[0].transpose(1, 0, 2).reshape(-1, W)

    def chain(x):
        return x[:, W:2 * W]

    for S in lengths:
        x = jnp.asarray(np.random.default_rng(S).normal(size=(S, 3 * W))
                        * 0.5, dtype=jnp.float32)
        sec = {}
        for name, f in (("chain", chain), ("rows_first", rows_first),
                        ("heads_first", heads_first)):
            loop = jax.jit(lambda x, n, f=f: jax.lax.fori_loop(
                0, n, lambda i, x: x.at[:, :W].set(f(x)), x))
            sec[name] = _marginal(
                lambda n, loop=loop: float(jax.device_get(loop(x, n)[0, 0])),
                16, 128)
        least = work.flash_prefill_least_s(
            {"prefill": [S]}, {"d_model": W, "n_layers": 1}, peak)
        new, old = (sec[k] - sec["chain"]
                    for k in ("rows_first", "heads_first"))
        b, bn = pallas_ops._rows_tiles(S, H, D, 4, (0, H, 2 * H))
        print(f"# kernel flash_attention fwd TOY PREFILL H={H} D={D} f32 "
              f"S={S}: rows-first (tile {b}, {bn} heads a step) "
              f"{new * 1e3:7.4f} ms ({100 * least / new:5.1f}% of roofline), "
              f"heads-first {old * 1e3:7.4f} ms "
              f"({100 * least / old:5.1f}%), least {least * 1e3:7.4f} ms, "
              f"chain {sec['chain'] * 1e3:7.4f} ms", flush=True)


def _bench_grouped_forward(peak: dict):
    """The serving prefill's shape (Command A+ in ``rag-steady``): one
    sequence of 2048 rows, 128 query heads over 8 K/V heads of 128, causal,
    bfloat16, the forward only; query head ``h`` reads K/V head ``h // 16``
    in place. The output feeds the next round's q (same shape, bounded)."""
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np

    from brpc_tpu.tpu.pallas_ops import flash_attention_mha

    H, G, S, D = 128, 8, 2048, 128
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(1, H, S, D)), dtype=jnp.bfloat16)
    k = jnp.asarray(rng.normal(size=(1, G, S, D)), dtype=jnp.bfloat16)
    v = jnp.asarray(rng.normal(size=(1, G, S, D)), dtype=jnp.bfloat16)

    @functools.partial(jax.jit, static_argnames=("n",))
    def loop(q, k, v, n: int):
        return jax.lax.fori_loop(
            0, n, lambda i, x: flash_attention_mha(x, k, v, causal=True,
                                                   interpret=False), q)

    def run(n):
        float(jax.device_get(loop(q, k, v, n)[0, 0, 0, 0]))

    sec = _marginal(run, 16, 128)
    tf = 2.0 * H * S * (S + 1) * D / sec / 1e12
    print(f"# kernel flash_attention fwd CAUSAL GROUPED H={H} over G={G} "
          f"S={S} D={D}: {sec * 1e3:7.3f} ms {tf:7.2f} TFLOP/s "
          f"({_pct_of_peak(tf, peak)})", flush=True)


def _bench_splash_control(q, k, v, causal_fwd_flops, peak: dict):
    """Public-kernel control: jax.experimental splash attention, same
    shape, causal — what the stock TPU kernel does on this chip (best
    effort: the module moves between JAX versions)."""
    import functools

    import jax
    import jax.numpy as jnp

    try:
        from jax.experimental.pallas.ops.tpu.splash_attention import (
            splash_attention_kernel as sk, splash_attention_mask as sm)
    except ImportError:
        return
    B, H, S, D = q.shape
    try:
        mask = sm.MultiHeadMask([sm.CausalMask((S, S))] * H)
        kernel = sk.make_splash_mha(mask=mask, head_shards=1,
                                    q_seq_shards=1)
        f = jax.vmap(lambda q1, k1, v1: kernel(q1 * (D ** -0.5), k1, v1))

        @functools.partial(jax.jit, static_argnames=("n",))
        def loop(q, k, v, n: int):
            def body(i, acc):
                q2 = q.at[0, 0, 0, 0].add(acc.astype(q.dtype))
                o = f(q2, k, v)
                return acc + o[0, 0, 0, 0].astype(jnp.float32) * 1e-6

            return jax.lax.fori_loop(0, n, body, jnp.float32(0))

        def run(n):
            float(jax.device_get(loop(q, k, v, n)))

        sec = _marginal(run, 16, 128)
        tf = causal_fwd_flops / sec / 1e12
        print(f"# control: public jax splash-attention fwd causal, same "
              f"shape: {tf:7.2f} TFLOP/s ({_pct_of_peak(tf, peak)})",
              flush=True)
    except Exception as e:
        print(f"# control: splash-attention unavailable "
              f"({type(e).__name__})", flush=True)


def bench_ring_path(peak: dict):
    """Ring-attention data path on the chip (VERDICT r4 #7): the same
    kernels the sp>1 shard_map runs — carry-form flash forward per KV hop
    (absolute-position causal masking) + per-hop Pallas backward with
    rotating dk/dv accumulation — replayed sequentially for every ring
    position, so the measured TFLOP/s is the ring lane's single-chip
    compute rate at the flagship shape (comm excluded: this is NOT the
    ring under shard_map, which tests_hw and chip_smoke.py run on real
    devices). Correctness of split-KV == whole-KV is
    tests_hw/test_hardware.py; this is the SPEED number."""
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np

    from brpc_tpu.tpu.pallas_ops import (_flash_bwd_bhsd, _flash_delta,
                                         flash_attention_carry)

    B, H, S, D, SP = 4, 8, 2048, 128, 4
    SQ = S // SP
    NEG_INF = -1e30
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(B, H, S, D)), dtype=jnp.bfloat16)
    k = jnp.asarray(rng.normal(size=(B, H, S, D)), dtype=jnp.bfloat16)
    v = jnp.asarray(rng.normal(size=(B, H, S, D)), dtype=jnp.bfloat16)

    def fwd_shard(d):
        """One ring position's forward: carry state across SP hops."""
        def f(q, k, v):
            qd = q[:, :, d * SQ:(d + 1) * SQ]
            m = jnp.full((B, H, SQ, 1), NEG_INF, jnp.float32)
            l = jnp.zeros((B, H, SQ, 1), jnp.float32)
            acc = jnp.zeros((B, H, SQ, D), jnp.float32)

            def one_head(q1, k1, v1, m1, l1, a1, ks):
                return flash_attention_carry(
                    q1, k1, v1, m1, l1, a1, d * SQ, ks, causal=True,
                    block_q=512, block_k=512, interpret=False)

            for hop in range(SP):
                src = (d - hop) % SP
                if src > d:
                    continue  # fully-future KV block: the ring's lax.cond
                    # skips the launch (tpu/ring.py); static here
                kb = k[:, :, src * SQ:(src + 1) * SQ]
                vb = v[:, :, src * SQ:(src + 1) * SQ]
                m, l, acc = jax.vmap(jax.vmap(
                    lambda a, b, c, x, y, z: one_head(
                        a, b, c, x, y, z, src * SQ)))(qd, kb, vb, m, l,
                                                      acc)
            safe = jnp.where(l == 0, 1.0, l)
            o = (acc / safe).astype(q.dtype)
            lse = jnp.where(l == 0, NEG_INF, m + jnp.log(safe))
            return o, lse
        return f

    @jax.jit
    def ring_fwd_bwd(q, k, v):
        dq_total = jnp.zeros((B, H, S, D), jnp.float32)
        dk_total = jnp.zeros((B, H, S, D), jnp.float32)
        dv_total = jnp.zeros((B, H, S, D), jnp.float32)
        out_sum = jnp.float32(0)
        for d in range(SP):
            o, lse = fwd_shard(d)(q, k, v)
            out_sum = out_sum + jnp.sum(o.astype(jnp.float32)) * 1e-6
            do = (o * jnp.bfloat16(1e-3)).astype(q.dtype)
            qb = q[:, :, d * SQ:(d + 1) * SQ].reshape(B * H, SQ, D)
            dob = do.reshape(B * H, SQ, D)
            lseb = lse.reshape(B * H, SQ, 1)
            deltab = _flash_delta(o.reshape(B * H, SQ, D), dob)
            dq_acc = jnp.zeros((B * H, SQ, D), jnp.float32)
            for hop in range(SP):
                src = (d - hop) % SP
                if src > d:
                    continue  # fully-future block: zero gradients
                kb = k[:, :, src * SQ:(src + 1) * SQ].reshape(
                    B * H, SQ, D)
                vb = v[:, :, src * SQ:(src + 1) * SQ].reshape(
                    B * H, SQ, D)
                dq_b, dk_b, dv_b = _flash_bwd_bhsd(
                    qb, kb, vb, lseb, dob, deltab, d * SQ, src * SQ,
                    True, 512, 512, False)
                dq_acc = dq_acc + dq_b.astype(jnp.float32)
                dk_total = dk_total.at[:, :, src * SQ:(src + 1) * SQ].add(
                    dk_b.reshape(B, H, SQ, D).astype(jnp.float32))
                dv_total = dv_total.at[:, :, src * SQ:(src + 1) * SQ].add(
                    dv_b.reshape(B, H, SQ, D).astype(jnp.float32))
            dq_total = dq_total.at[:, :, d * SQ:(d + 1) * SQ].set(
                dq_acc.reshape(B, H, SQ, D))
        return (out_sum + jnp.sum(dq_total[0, 0, 0]) * 1e-9
                + jnp.sum(dk_total[0, 0, 0]) * 1e-9
                + jnp.sum(dv_total[0, 0, 0]) * 1e-9)

    @functools.partial(jax.jit, static_argnames=("n",))
    def loop(q, k, v, n: int):
        def body(i, accv):
            q2 = q.at[0, 0, 0, 0].add(accv.astype(q.dtype))
            return accv + ring_fwd_bwd(q2, k, v) * 1e-6

        return jax.lax.fori_loop(0, n, body, jnp.float32(0))

    def run(n):
        float(jax.device_get(loop(q, k, v, n)))

    sec = _marginal(run, 16, 128)
    # causal useful flops, fwd (2 matmuls) + bwd (5 matmuls)
    flops = 3.5 * 2.0 * B * H * S * (S + 1) * D
    tf = flops / sec / 1e12
    print(f"# ring-attention path fwd+bwd CAUSAL sp={SP} (carry-kernel "
          f"hops + per-hop Pallas backward) B={B} H={H} S={S} D={D}: "
          f"{tf:7.2f} TFLOP/s ({_pct_of_peak(tf, peak)})", flush=True)
    return tf


def bench_ring_hops(peak: dict):
    """The ring itself on four chips: ONE layer of train-16k-sp4's
    attention (B=1, S=16384 over sp=4, 16 heads of 128, bfloat16, causal,
    flash) under ring_attention's own shard_map, forward alone and forward
    + backward. For each chip: a pass's time (host clock over the
    repeats), the sum of its flash kernels and the time a collective holds
    its op line (both from a profiler trace, read with the benchmark's
    trace_reduce), beside the wire time the pass's bytes need at the link
    rate measured here by a ring of bare ppermutes. Both layouts of the
    rows (ring.LAYOUTS; q, k and v are random, so the layout is only what
    the call states): contiguous, where chip 0 runs one block a pass under
    the causal mask and chip 3 four, and zigzag, which levels the
    kernels' sums (the train step's)."""
    import functools
    import shutil
    import tempfile

    import jax
    import jax.numpy as jnp
    from jax import lax, shard_map
    from jax.sharding import NamedSharding, PartitionSpec as P

    from benchmark.harness import trace_reduce
    from brpc_tpu.tpu import mesh as meshlib
    from brpc_tpu.tpu.ring import LAYOUTS, ring_attention

    SP, B, S, H, D, REPS = 4, 1, 16384, 16, 128, 8
    if len(jax.devices()) < SP:
        print(f"# ring_hops needs {SP} chips, found {len(jax.devices())}: "
              "skipped", flush=True)
        return None
    mesh = meshlib.make_mesh({"sp": SP}, jax.devices()[:SP])
    shard = NamedSharding(mesh, P(None, "sp", None, None))
    make = jax.jit(lambda key: jax.random.normal(key, (B, S, H, D),
                                                 jnp.bfloat16),
                   out_shardings=shard)
    q, k, v = (make(jax.random.PRNGKey(i)) for i in range(3))

    def passes(layout):
        def attend(q, k, v):
            return ring_attention(q, k, v, mesh, "sp", causal=True,
                                  use_flash=True, layout=layout)

        return (("fwd", jax.jit(attend)), ("fwd+bwd", jax.jit(jax.grad(
            lambda q, k, v: jnp.sum(attend(q, k, v).astype(jnp.float32)),
            argnums=(0, 1, 2)))))

    # the link: K and V's rotation and nothing else, chained
    perm = [(i, (i + 1) % SP) for i in range(SP)]

    @functools.partial(jax.jit, static_argnames=("n",))
    def rotate(k, v, n: int):
        @functools.partial(shard_map, mesh=mesh, in_specs=(shard.spec,) * 2,
                           out_specs=(shard.spec,) * 2)
        def f(k, v):
            return lax.fori_loop(0, n, lambda i, kv: tuple(
                lax.ppermute(x, "sp", perm) for x in kv), (k, v))
        return f(k, v)

    block = B * (S // SP) * H * D                 # elements a chip holds
    sec = _marginal(lambda n: jax.block_until_ready(rotate(k, v, n)), 8, 64)
    link = 2 * block * 2 / sec                    # bytes/s a chip sends
    narrow, wide = 2 * block * 2, 2 * block * 4   # a K+V or dK+dV pair
    sent = {"fwd": (SP - 1) * narrow,
            "fwd+bwd": 2 * (SP - 1) * narrow + 2 * narrow
            + (SP - 2) * wide}
    print(f"# ring_hops sp={SP} B={B} S={S} H={H} D={D} bf16 causal: link "
          f"{link / 1e9:.1f} GB/s a chip one way (bare ppermute of K+V, "
          f"{narrow / 1e6:.1f} MB in {sec * 1e3:.3f} ms)", flush=True)
    for layout in LAYOUTS:
        for name, fn in passes(layout):
            jax.block_until_ready(fn(q, k, v))        # compile
            tdir = tempfile.mkdtemp(prefix="ring_hops_")
            jax.profiler.start_trace(tdir)
            t0 = time.perf_counter()
            jax.block_until_ready([fn(q, k, v) for _ in range(REPS)])
            wall = (time.perf_counter() - t0) / REPS
            jax.profiler.stop_trace()
            red = trace_reduce.Reduced(trace_reduce.extract(
                trace_reduce.find_xplane(tdir)))
            shutil.rmtree(tdir, ignore_errors=True)
            for dev in red.devices:
                print(f"# ring_hops {layout:10s} {name:7s} chip {dev}: pass "
                      f"{wall * 1e3:7.3f} ms, busy "
                      f"{red.busy_s(dev) / REPS * 1e3:7.3f}, flash kernels "
                      f"{red.op_ns('flash', dev)[0] / REPS / 1e6:7.3f}, "
                      f"collectives on the op line "
                      f"{red.collective_ns(dev) / REPS / 1e6:7.3f}; its "
                      f"{sent[name] / 1e6:.1f} MB need "
                      f"{sent[name] / link * 1e3:.3f} ms of wire",
                      flush=True)
    return link


def bench_rmsnorm(peak: dict):
    """Chained-carry bandwidth, reported against the measured Mosaic DMA
    ceiling (a pure-copy Pallas kernel) AND the XLA wire (fused add)."""
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from brpc_tpu.tpu.pallas_ops import rmsnorm

    N, D = 65536, 2048  # 256MB bf16: no cache can hold it — true HBM
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(N, D)), dtype=jnp.bfloat16)
    w = jnp.asarray(rng.normal(size=(D,)), dtype=jnp.bfloat16)

    def chained(call):
        @functools.partial(jax.jit, static_argnames=("n",))
        def loop(x, w, n: int):
            def body(i, xc):
                return call(xc, w)

            return jax.lax.fori_loop(0, n, body, x)

        def run(n):
            jax.device_get(loop(x, w, n)[0, :1])

        sec = _marginal(run, 64, 512)
        return 2.0 * N * D * 2 / sec / 1e9  # bf16 read + write

    gbps = chained(lambda xc, w: rmsnorm(xc, w, interpret=False,
                                         block_rows=512))

    rows = 512

    def _copy_kernel(x_ref, o_ref):
        o_ref[:] = x_ref[:]

    def copy_call(xc, w):
        return pl.pallas_call(
            _copy_kernel, grid=(N // rows,),
            in_specs=[pl.BlockSpec((rows, D), lambda i: (i, 0))],
            out_specs=pl.BlockSpec((rows, D), lambda i: (i, 0)),
            out_shape=jax.ShapeDtypeStruct((N, D), xc.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel",)))(xc)

    ceil = chained(copy_call)
    xla = chained(lambda xc, w: xc + jnp.bfloat16(1))
    hbm = peak["hbm_bytes_per_s"] / 1e9
    print(f"# kernel rmsnorm {N}x{D}: {gbps:7.1f} GB/s HBM "
          f"({gbps/hbm*100:.0f}% of the {hbm:.0f} GB/s HBM peak; "
          f"{gbps/ceil*100:.0f}% of a pure-copy Pallas kernel at "
          f"{ceil:.0f} GB/s; XLA elementwise wire = {xla:.0f} GB/s — "
          f"docs/round4-notes.md §3)", flush=True)
    return gbps


def bench_paged_decode(peak: dict):
    """Decode attention over the pages in place, at the widths of the
    `chat-steady` cell (d_model 2048, 16 heads, 12 layers x 2048 blocks of
    16 float32 rows): the kernel alone, chained through its own output,
    against the bytes of the pages the rows' lengths cover; the gather
    body beside it on the same rows."""
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np

    from brpc_tpu.serving.model import _gather_attention
    from brpc_tpu.tpu.pallas_ops import paged_decode_attention

    H, D, bs, layers, blocks = 16, 2048, 16, 12, 2048
    key = jax.random.key(0)
    shape = (layers, (blocks + 1) * bs, D)
    kpool = jax.random.normal(jax.random.fold_in(key, 1), shape, jnp.float32)
    vpool = jax.random.normal(jax.random.fold_in(key, 2), shape, jnp.float32)
    rng = np.random.default_rng(0)
    hbm = peak["hbm_bytes_per_s"]

    def case(label, rows, context, lengths, table_pages=None):
        # ``table_pages``: the width serving hands the kernel (its tables
        # come in multiples of PAGED_TABLE_PAGES); the gather body always
        # copies the context bucket
        n_pages = table_pages or context // bs
        lengths = np.asarray(lengths, np.int32)
        pad = np.ones(rows - len(lengths), np.int32)
        lens = jnp.asarray(np.concatenate([lengths, pad]))
        ids = rng.permutation(np.arange(1, blocks + 1))
        tables = np.zeros((rows, n_pages), np.int32)
        used = 0
        for b, n in enumerate(lengths):
            live = -(-int(n) // bs)
            tables[b, :live] = ids[used:used + live]
            used += live
        tables = jnp.asarray(tables)
        slots = (tables[:, :context // bs, None] * bs
                 + jnp.arange(bs)).reshape(rows, -1)
        mask = jnp.arange(context)[None, :] < lens[:, None]
        q0 = jax.random.normal(jax.random.fold_in(key, 3), (rows, D),
                               jnp.float32)

        def chained(call):
            @functools.partial(jax.jit, static_argnames=("n",))
            def loop(q, kp, vp, n: int):
                def body(i, qc):
                    return call(qc, kp, vp, i % layers)
                return jax.lax.fori_loop(0, n, body, q)

            def run(n):
                jax.device_get(loop(q0, kpool, vpool, n)[0, :1])

            return _marginal(run, 24, 240)

        paged = chained(lambda q, kp, vp, l: paged_decode_attention(
            q, kp, vp, l, tables, lens, n_heads=H, block_size=bs,
            interpret=False))
        gather = chained(lambda q, kp, vp, l: _gather_attention(
            q, kp[l], vp[l], slots, mask, H))
        live_bytes = 2 * 4 * D * float(np.sum(-(-lengths // bs)) * bs)
        print(f"# kernel paged_decode_attention {label} ({rows} x {context}"
              f" bucket, table {n_pages} pages, {len(lengths)} live rows, "
              f"{int(lengths.sum())} positions): {paged * 1e6:8.1f} us a "
              f"layer = {live_bytes / paged / 1e9:6.1f} GB/s of live pages "
              f"({live_bytes / paged / hbm * 100:.0f}% of the HBM peak); "
              f"the gather body on the same rows {gather * 1e6:8.1f} us",
              flush=True)

    chat = [957, 612, 402, 301, 222, 131, 57]   # ~7 rows of 50-960
    case("chat-steady rows", 8, 1024, chat)
    case("chat-steady rows, upper bucket", 16, 1024,
         chat + [880, 45, 512])
    case("every row full", 8, 1024, [1024] * 8)
    case("two short rows", 2, 512, [80, 300])
    # as served: the same rows under tables of 128 pages
    case("chat-steady rows as served", 8, 1024, chat, table_pages=128)
    case("upper bucket as served", 16, 1024, chat + [880, 45, 512],
         table_pages=128)
    case("two short rows as served", 2, 512, [80, 300], table_pages=128)
    _bench_paged_grouped(peak)


def _bench_paged_grouped(peak: dict):
    """The same kernel at the `rag-steady` cell's widths (Command A+: 128
    query heads over 8 key/value heads of 128, bfloat16 pools, 8 rows), as
    the model calls it: tables of the served context (512 pages, 8192
    rows) for the full layer, a window layer's ring of 257 pages (4112
    rows, window 4096), the grid the rows' live steps (``fetch_plan``).
    Beside it the same kernel walking every column of the table, and the
    path the kernel replaced in ``Cohere2MoeModel``'s decode: the context
    gathered whole blocks at a time, padded to the batch's context bucket
    (``_block_context``), then the grouped einsum and ``_softmax_out``.
    Each against the bytes of the live rows (pages the kernel fetches)
    over the HBM peak."""
    import functools
    import math

    import jax
    import jax.numpy as jnp
    import numpy as np

    from brpc_tpu.serving.hybrid_model import _block_context, _mm, _ring_live
    from brpc_tpu.serving.moe_model import _softmax_out
    from brpc_tpu.tpu.pallas_ops import paged_decode_attention

    H, G, hd, bs, rows, table = 128, 8, 128, 16, 8, 512
    ring = (4112, 4096)
    key = jax.random.key(1)
    hbm = peak["hbm_bytes_per_s"]
    blocks = rows * table + 1
    kpool, vpool = (jax.random.normal(
        jax.random.fold_in(key, i), (3, (blocks + 1) * bs, G * hd),
        jnp.bfloat16) for i in (1, 2))
    ids = np.random.default_rng(1).permutation(np.arange(1, blocks + 1))
    tables = jnp.asarray(ids[:rows * table].reshape(rows, table), jnp.int32)
    q0 = jax.random.normal(jax.random.fold_in(key, 3), (rows, H * hd),
                           jnp.float32)

    def gather(q, kp, vp, layer, tab, lens, ring):
        kc = _block_context(kp, layer, tab, bs)
        vc = _block_context(vp, layer, tab, bs)
        pos = lens - 1
        if ring is None:
            live = jnp.arange(kc.shape[1])[None, :] <= pos[:, None]
        else:
            live = _ring_live(pos, *ring)[:, :kc.shape[1]]
        kh, vh = (c.reshape(rows, -1, G, hd) for c in (kc, vc))
        sc = _mm(q.reshape(rows, G, H // G, hd), kh,
                 "bgjd,bkgd->bgjk") / math.sqrt(hd)
        return _softmax_out(sc, live[:, None, None, :], vh,
                            "bgjk,bkgd->bgjd").reshape(rows, -1)

    def chained(call):
        @functools.partial(jax.jit, static_argnames=("n",))
        def loop(q, kp, vp, n: int):
            def body(i, qc):
                return call(qc, kp, vp, i % 3)
            return jax.lax.fori_loop(0, n, body, q)

        def run(n):
            jax.device_get(loop(q0, kpool, vpool, n)[0, :1])

        return _marginal(run, 12, 120)

    def case(label, lengths, bucket, ring=None):
        lengths = np.asarray(lengths, np.int32)
        lens = jnp.asarray(lengths)
        tab = tables[:, :-(-ring[0] // bs)] if ring else tables
        # the gather read the ring's rows under the batch's context bucket
        gt = tab[:, :min(bucket, ring[0] if ring else bucket) // bs]
        fetched = np.minimum(lengths, ring[0]) if ring else lengths
        live_bytes = 2 * 2 * G * hd * float((-(-fetched // bs)).sum() * bs)
        t = {walk: chained(lambda q, kp, vp, l, plan=plan:
                           paged_decode_attention(
                               q, kp, vp, l, tab, lens, n_heads=H,
                               n_kv_heads=G, block_size=bs, interpret=False,
                               ring=ring, fetch_plan=plan))
             for walk, plan in (("plan", True), ("grid", False))}
        t["gather"] = chained(lambda q, kp, vp, l: gather(q, kp, vp, l, gt,
                                                          lens, ring))
        print(f"# kernel paged_decode_attention {label} (Command A+, {rows}"
              f" rows, 128 over 8 heads of 128, bfloat16, table "
              f"{tab.shape[1]} pages): {t['plan'] * 1e6:8.1f} us a layer = "
              f"{live_bytes / t['plan'] / 1e9:6.1f} GB/s of live pages "
              f"({live_bytes / t['plan'] / hbm * 100:.0f}% of the HBM "
              f"peak); every column walked {t['grid'] * 1e6:8.1f} us; the "
              f"gather + grouped einsum at a {bucket}-row bucket "
              f"{t['gather'] * 1e6:8.1f} us", flush=True)

    wide = [512, 6912, 1210, 905, 2403, 700, 3101, 1499]
    case("full layer, rows of 512-6912", wide, 8192)
    case("window ring, rows of 512-6912", wide, 8192, ring)
    # `rag-steady`'s batch: ~3.8 live rows of ~1 200, the rest padded
    rag = [1100, 1350, 900, 1500, 1, 1, 1, 1]
    case("full layer, 4 rows of 900-1500 and 4 padded", rag, 2048)
    case("window ring, 4 rows of 900-1500 and 4 padded", rag, 2048, ring)
    # what a step the grid walks past a row's end costs: rows of one page
    case("full layer, 8 rows of one page", [16] * 8, 1024)


def bench_ssm_scan(peak: dict):
    """The prefill's selective scan alone, the kernel
    (``pallas_ops.ssm_scan``, what ``hybrid_model.ssm_scan`` hands off to)
    and the XLA form beside it (``ssm_scan_reference``), d_inner 5120,
    d_state 16, float32, chained through the state each returns: at one
    chunk of the `longdoc-steady` cell (2048 rows from a NON-ZERO state, a
    later chunk of a prompt) and at `reason-steady`'s window-sized and
    longest prefill buckets (512 and 3072 rows). Each against the least
    time the chip could take for dt, u and y once over the HBM peak (B and
    C are 16 wide; the (rows, 16, 5120) decay and drive need never leave
    the chip), and as the rate of the block's own count of a row's
    elementwise operations (``blocks/jamba/work.py:scan_row_flops``: 7 a
    (channel, state) element and the conv's 8 a channel). Read by no
    metric."""
    import functools

    import jax
    import jax.numpy as jnp

    from brpc_tpu.tpu.pallas_ops import ssm_scan, ssm_scan_reference

    # the block's own count; appended, so it shadows no module of another
    # bench in this process
    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark")
    if bench not in sys.path:
        sys.path.append(bench)
    from blocks.jamba.work import scan_row_flops

    di, n = 5120, 16
    key = jax.random.key(0)

    def draw(i, shape, scale=1.0):
        return scale * jax.random.normal(jax.random.fold_in(key, i), shape,
                                         jnp.float32)

    a = -jnp.broadcast_to(jnp.arange(1.0, n + 1)[:, None], (n, di))
    s0 = draw(5, (n, di), 0.1)
    for rows in (2048, 512, 3072):
        dt = jax.nn.softplus(draw(1, (rows, di)) - 4.0)      # ~2e-2
        u = draw(2, (rows, di))
        bm, cm = draw(3, (rows, n)), draw(4, (rows, n))
        least = 3 * rows * di * 4 / peak["hbm_bytes_per_s"]
        flops = rows * scan_row_flops({"di": di, "kc": 4, "n": n})
        for name, scan, lo, hi in (("kernel", ssm_scan, 8, 48),
                                   ("XLA reference", ssm_scan_reference, 4,
                                    16)):
            @functools.partial(jax.jit, static_argnames=("k",))
            def loop(s, k: int, scan=scan):
                def body(_i, carry):
                    s, acc = carry
                    s, y = scan(dt, u, bm, cm, a, s)
                    return s, acc + y[-1]
                return jax.lax.fori_loop(0, k, body, (s, jnp.zeros(di)))

            def run(k):
                jax.device_get(loop(s0, k)[1][:1])

            secs = _marginal(run, lo, hi)
            print(f"# kernel ssm_scan ({name}) {rows} rows x d_inner {di} x "
                  f"d_state {n} float32 from a non-zero state: "
                  f"{secs * 1e3:7.3f} ms a layer "
                  f"({rows / secs / 1e6:.2f} M rows/s); dt, u and y once "
                  f"over the HBM peak {least * 1e3:.3f} ms "
                  f"({least / secs * 100:.1f}% of that roofline); "
                  f"scan_row_flops {flops / 1e9:.2f} GFLOP = "
                  f"{flops / secs / 1e9:.0f} GFLOP/s", flush=True)


def bench_cca_mix(peak: dict):
    """The decode-side mix of the ``zaya`` lane alone
    (``zaya_model.cca_mix`` behind the tail shift: both convs, the q-k mean,
    the L2 norms, the temperature, rotary and the value shift) at the
    `solve-steady` cell's shape: 32 rows, 20 layers' worth in one
    ``fori_loop`` over stacked weights, each layer from a NON-ZERO tail that
    it shifts, the published widths (8-over-2 heads of 128, so 1280 mixed
    channels and a tail of 2688 floats a row a layer). XLA fusions today, so
    a later fused kernel has its number to beat; against the least time for
    the tails read and written and the convs' weights once over the HBM
    peak. Read by no metric."""
    import functools

    import jax
    import jax.numpy as jnp

    from brpc_tpu.serving.zaya_model import ZayaConfig, cca_mix

    cfg = ZayaConfig(hidden_size=2048, num_attention_heads=8,
                     num_key_value_heads=2, head_dim=128,
                     moe_intermediate_size=2048, num_experts=16,
                     router_hidden_size=256, num_hidden_layers=20,
                     vocab_size=262272)
    rows, n, qk, hd = 32, cfg.n_layers, cfg.qk_dim, cfg.head_dim
    cut = cfg.back * qk
    key = jax.random.key(0)

    def draw(i, shape, scale=1.0, at=0.0):
        return at + scale * jax.random.normal(jax.random.fold_in(key, i),
                                              shape, jnp.float32)

    w = {"c0w": draw(1, (n, cfg.t0, qk), 0.1, 0.5),
         "c0b": draw(2, (n, qk), 0.1),
         "c1w": draw(3, (n, cfg.t1, qk // hd, hd, hd), 0.5 / 16),
         "c1b": draw(4, (n, qk), 0.1), "temp": draw(5, (n, 2), 0.1, 1.0)}
    w = {k: v.astype(jnp.bfloat16) for k, v in w.items()}   # as stored
    proj0 = draw(6, (rows, qk + 2 * hd))
    tails0 = draw(7, (n, rows, cfg.tail_width))
    pos = jnp.arange(rows, dtype=jnp.int32) + 1000

    @functools.partial(jax.jit, static_argnames=("k",))
    def loop(proj, tails, k: int):
        def layer(i, carry):
            proj, tails = carry
            wl = {name: v[i].astype(jnp.float32) for name, v in w.items()}
            tail = tails[i]
            windows = jnp.concatenate(
                [tail[:, :cut].reshape(rows, cfg.back, qk),
                 proj[:, None, :qk]], axis=1)
            q, kk, v = cca_mix(cfg, wl, windows, proj[:, qk:qk + hd],
                               tail[:, cut:], pos)
            tails = tails.at[i].set(jnp.concatenate(
                [windows[:, 1:].reshape(rows, cut), proj[:, qk + hd:]],
                axis=-1))
            # the next layer's rows depend on this one's mix
            return proj + 1e-3 * jnp.concatenate(
                [q.reshape(rows, -1), kk, v], axis=-1), tails

        def many(_j, carry):
            return jax.lax.fori_loop(0, n, layer, carry)

        return jax.lax.fori_loop(0, k, many, (proj, tails))

    def run(k):
        jax.device_get(loop(proj0, tails0, k)[0][:1, :1])

    secs = _marginal(run, 8, 40)
    nbytes = 2 * tails0.size * 4 + sum(v.size * 2 for v in w.values())
    least = nbytes / peak["hbm_bytes_per_s"]
    print(f"# cca_mix (XLA fusions) {rows} rows x {n} layers from a non-zero "
          f"tail, {qk} mixed channels, tail {cfg.tail_width} floats: "
          f"{secs * 1e3:7.3f} ms a step's worth ({secs / n * 1e6:.1f} us a "
          f"layer); tails in and out and the convs' weights once over the "
          f"HBM peak {least * 1e3:.3f} ms ({least / secs * 100:.1f}% of "
          f"that roofline)", flush=True)


def bench_sambay_decode(peak: dict):
    """SambaY's whole decode program alone (``SambaYModel._decode_fn``) at
    the `reason-steady` cell's shape: the configuration's 16 layers at the
    published widths, float32 weights drawn on the device, its pools as
    deployed (16384 full-layer blocks, rings for 64 sequences), 8 rows whose
    rings have wrapped, at contexts of 1024 and 2048. Both forms of the
    context read side by side: the block form the program has (whole blocks,
    the layer inside the gather's index, attention over K where the gather
    puts it) and the row form it replaced (``pool[layer]``, then one row a
    position, then ``_attend_rows``' heads-before-K einsums), swapped in
    here alone. Against the least time of the block's own byte count
    (``blocks/sambay/work.py:decode_step_bytes``: every weight once, the
    live K/V rows and state) over the HBM peak. Read by no metric."""
    import functools
    import json

    import jax
    import jax.numpy as jnp
    import numpy as np

    from brpc_tpu.serving import hybrid_cache, hybrid_model as hm

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    bench = os.path.join(root, "benchmark")
    if bench not in sys.path:
        sys.path.append(bench)
    from blocks.sambay.work import decode_step_bytes

    with open(os.path.join(bench, "configs",
                           "phi4-mini-flash-l16-serve.json")) as f:
        args = json.load(f)["runner_args"]
    margs = {k: v for k, v in args["model"].items() if k != "rehearsal"}
    kargs = {k: v for k, v in args["kv"].items() if k != "rehearsal"}
    cfg = hm.SambaYConfig(**margs)
    kv = cfg.cache(hybrid_cache.HybridCacheConfig(**kargs,
                                                  window=cfg.window))
    model = hm.SambaYModel.__new__(hm.SambaYModel)
    model._init_programs(cfg, kv)
    key = jax.random.key(0)
    w = {name: 0.02 * jax.random.normal(jax.random.fold_in(key, i), shape,
                                        jnp.float32)
         for i, (name, shape, _how) in enumerate(cfg.weight_specs())}
    wbytes = sum(v.size * 4 for v in w.values())
    bs, rows = kv.block_size, 8
    rng = np.random.default_rng(0)

    def rows_context(pool, layer, blocks, block_size):
        t = jnp.arange(blocks.shape[1] * block_size)
        return pool[layer][blocks[:, t // block_size] * block_size
                           + t % block_size]

    def rows_attend(cfg, qh, kc, vc, live):
        kh, vh = hm._kv_heads(cfg, kc, vc)
        return jax.vmap(functools.partial(hm._attend_rows, cfg))(
            qh[:, None], kh, vh, live[:, None, :])[:, 0]

    forms = {"block form": (hm._block_context, hm._attend_decode),
             "row form (before)": (rows_context, rows_attend)}
    # the pools and slots, donated to each launch and taken back from it;
    # the last entry: the tokens the next launch reads
    state = [kv.full.k_pool, kv.full.v_pool, kv.window.k_pool,
             kv.window.v_pool, kv.ssm, kv.conv, jnp.ones(rows, jnp.int32)]
    for context in (1024, 2048):
        pos = np.sort(rng.integers(context // 2, context, rows)).astype(
            np.int32)
        blocks = rng.permutation(np.arange(1, kv.num_blocks + 1))[
            :rows * context // bs].reshape(rows, -1).astype(np.int32)
        rings = rng.permutation(np.arange(1, kv.window.num_blocks + 1))[
            :rows * kv.ring_blocks].reshape(rows, -1).astype(np.int32)
        host = (pos, blocks, rings, np.arange(1, rows + 1, dtype=np.int32))
        least = decode_step_bytes(pos + 1, margs) / peak["hbm_bytes_per_s"]
        line = []
        for label, read in forms.items():
            hm._block_context, hm._attend_decode = read
            try:
                fn = model._decode_fn(rows, context).lower(
                    w, *state, *host).compile()
            finally:
                hm._block_context, hm._attend_decode = forms["block form"]

            def run(n, fn=fn):
                for _ in range(n):
                    state[:] = fn(w, *state, *host)
                jax.device_get(state[-1][:1])

            secs = _marginal(run, 4, 24)
            line.append(f"{label} {secs * 1e3:.3f} ms "
                        f"({least / secs * 100:.1f}% of that least time)")
        print(f"# sambay_decode {rows} rows x {context} context "
              f"(positions {pos.min()}-{pos.max()}), {cfg.n_layers} layers, "
              f"{wbytes / 1e9:.2f} GB of float32 weights: least time "
              f"{least * 1e3:.3f} ms (weights once + live K/V and state "
              f"over the HBM peak; the weights alone "
              f"{wbytes / peak['hbm_bytes_per_s'] * 1e3:.3f} ms); "
              + "; ".join(line), flush=True)


def bench_mla_paged_decode(peak: dict):
    """The absorbed decode attention over latent pages alone, at the widths
    of the `agent-steady` cell (20 heads over rows of 576 values allocated
    at 640, 24 layers x 12288 blocks of 16 bfloat16 rows): the kernel
    chained through its own output, against the least time of the rows'
    lengths (each live row once, 1152 B, over the HBM peak), at several
    chunks of the kernel's loop."""
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np

    from brpc_tpu.tpu.pallas_ops import MLA_CHUNK, mla_paged_decode

    H, lat, wide, d_v, bs, layers, blocks = 20, 576, 640, 512, 16, 24, 12288
    key = jax.random.key(0)
    pool = jax.random.normal(jax.random.fold_in(key, 1),
                             (layers, (blocks + 1) * bs, wide), jnp.bfloat16)
    rng = np.random.default_rng(0)
    hbm = peak["hbm_bytes_per_s"]

    def case(label, rows, lengths, table_rows, chunk):
        lengths = np.asarray(lengths, np.int32)
        pad = np.ones(rows - len(lengths), np.int32)
        lens = jnp.asarray(np.concatenate([lengths, pad]))
        ids = rng.permutation(np.arange(1, blocks + 1))
        tables = np.zeros((rows, table_rows // bs), np.int32)
        used = 0
        for b, n in enumerate(lengths):
            live = -(-int(n) // bs)
            tables[b, :live] = ids[used:used + live]
            used += live
        tables = jnp.asarray(tables)
        q0 = jax.random.normal(jax.random.fold_in(key, 3), (rows, H, wide),
                               jnp.float32)

        @functools.partial(jax.jit, static_argnames=("n",))
        def loop(q, pl_, n: int):
            def body(i, qc):
                out = mla_paged_decode(qc, pl_, i % layers, tables, lens,
                                       block_size=bs, d_v=d_v,
                                       scale=1.0 / 16, chunk=chunk,
                                       interpret=False)
                # chained: the next query is a function of this output
                return qc.at[:, :, :d_v].set(out * 0.5)
            return jax.lax.fori_loop(0, n, body, q)

        def run(n):
            jax.device_get(loop(q0, pool, n)[0, 0, :1])

        took = _marginal(run, 24, 240)
        live_bytes = 2.0 * lat * float(lengths.sum())
        print(f"# kernel mla_paged_decode {label} ({rows} rows, table "
              f"{table_rows} positions, {int(lengths.sum())} live latent "
              f"rows, chunk {chunk}): {took * 1e6:8.1f} us a layer, least "
              f"{live_bytes / hbm * 1e6:7.1f} us = "
              f"{live_bytes / took / hbm * 100:.0f}% of the roofline "
              f"({live_bytes / took / 1e9:6.1f} GB/s of latent rows)",
              flush=True)

    agent = rng.lognormal(np.log(6144), 0.7, size=16).clip(1024, 24576)
    for chunk in sorted({256, 512, MLA_CHUNK, 2048}):
        case("the cell's shape", 16, [8192] * 16, 8192, chunk)
    case("the cell's shape, the widest table", 16, [8192] * 16, 32768,
         MLA_CHUNK)
    case("agent-steady rows", 16, agent.astype(np.int32), 32768, MLA_CHUNK)
    case("few short rows in a wide batch", 32, [1500, 3000, 900, 5000],
         32768, MLA_CHUNK)


def bench_mla_chunk(peak: dict):
    """A later prefill chunk's latent attention alone, at the widths of the
    `agent-steady` cell (20 heads of 192 + 64 rotary, latent 512 in rows
    allocated at 640, values 256, bfloat16): the kernel
    (``pallas_ops.mla_chunk_attention``) at several tiles beside the path it
    replaced (PR 41's, copied here: a ``lax.map`` over the heads of the XLA
    expansion of the whole context bucket, then ``flash_attention_carry`` at
    512 x 512), each chained through its own output. A chunk of 2048 rows
    at 2048 / 6144 / 14336 / 22528 in buckets of 4096 / 8192 / 16384 /
    32768. Against the least operations: (query, key) pairs x 1024 x H and
    the rows up to the chunk's end x 2 x 512 x 448 x H (their K and V built
    once)."""
    import functools

    import jax
    import jax.numpy as jnp

    from brpc_tpu.tpu.pallas_ops import (NEG_INF, flash_attention_carry,
                                         mla_chunk_attention)

    H, r, nope, hd, d_v, wide, c = 20, 512, 192, 256, 256, 640, 2048
    bf = jnp.bfloat16
    key = jax.random.key(0)
    wuk = (jax.random.normal(jax.random.fold_in(key, 1), (H, r, nope))
           / r ** 0.5).astype(bf)
    wuv = (jax.random.normal(jax.random.fold_in(key, 2), (H, r, d_v))
           / r ** 0.5).astype(bf)
    q0 = jax.random.normal(jax.random.fold_in(key, 3), (c, H * hd), bf)

    def parent(q, ctx, start):
        lat, k_r = ctx[:, :r], ctx[:, r:r + hd - nope]
        m0 = jnp.full((c, 1), NEG_INF, jnp.float32)
        l0 = jnp.zeros((c, 1), jnp.float32)
        acc0 = jnp.zeros((c, d_v), jnp.float32)

        def head(args):
            qh, wk, wv = args
            k = jnp.concatenate([jnp.matmul(
                lat, wk, preferred_element_type=jnp.float32).astype(bf),
                k_r], axis=-1)
            v = jnp.matmul(lat, wv,
                           preferred_element_type=jnp.float32).astype(bf)
            _m, l, acc = flash_attention_carry(
                qh, k, v, m0, l0, acc0, start, 0, causal=True,
                block_q=512, block_k=512, interpret=False)
            return acc / l

        out = jax.lax.map(head, (q.reshape(c, H, hd).transpose(1, 0, 2),
                                 wuk, wuv))
        return out.transpose(1, 0, 2).reshape(c, H * d_v).astype(bf)

    def kernel(bq, bk):
        return lambda q, ctx, start: mla_chunk_attention(
            q, ctx, wuk, wuv, start, c, block_q=bq, block_k=bk,
            interpret=False)

    paths = [("parent's path", parent)] + [
        (f"kernel {bq} x {bk}", kernel(bq, bk))
        for bq, bk in ((512, 512), (1024, 512), (2048, 512), (512, 1024),
                       (1024, 1024))]
    total = {name: 0.0 for name, _ in paths}
    least_total = 0.0
    for start, bucket in ((2048, 4096), (6144, 8192), (14336, 16384),
                          (22528, 32768)):
        ctx = jax.random.normal(jax.random.fold_in(key, bucket),
                                (bucket, wide), jnp.float32)
        ctx = ctx.at[:, r + hd - nope:].set(0.0).astype(bf)
        pairs = c * start + c * (c + 1) // 2
        flops = H * (pairs * 4 * hd + (start + c) * 2 * r * (nope + d_v))
        least = flops / peak["bf16_flops"]
        least_total += least
        for name, fn in paths:
            @functools.partial(jax.jit, static_argnames=("n",))
            def loop(q, n: int, fn=fn):
                return jax.lax.fori_loop(
                    0, n, lambda _i, qc: fn(qc, ctx, jnp.int32(start)), q)

            def run(n):
                jax.device_get(loop(q0, n)[0, :1])

            took = _marginal(run, 2, 10, reps=3)
            total[name] += took
            print(f"# kernel mla_chunk {name}: chunk {c} at {start} in a "
                  f"bucket of {bucket}: {took * 1e3:8.3f} ms a layer, "
                  f"least {least * 1e3:.3f} ms = "
                  f"{least / took * 100:.1f}% of the MXU peak "
                  f"({flops / 1e12:.2f} TFLOP)", flush=True)
    base = total["parent's path"]
    for name, took in total.items():
        print(f"# kernel mla_chunk {name}, summed over the four shapes: "
              f"{took * 1e3:8.3f} ms a layer, {took / base * 100:.1f}% of "
              f"the parent's path, {least_total / took * 100:.1f}% of the "
              f"MXU peak", flush=True)


def bench_train_step_mfu(peak: dict):
    """Single-chip train step of the flagship LM, reported BOTH ways:
    kernels ON (Pallas flash fwd+bwd, Pallas norm, fused xent — the
    shipping config) and the plain-XLA baseline (use_flash_attention=False).
    Config uses n_heads=8 (head_dim 128): the MXU contracts 128 deep, so
    64-wide heads would leave half the systolic array dark."""
    import functools

    import jax
    import jax.numpy as jnp

    from brpc_tpu.tpu import train

    B, S = 8, 1024

    def measure(cfg, batch=B, hi=12):
        # hi sets the measured work: the marginal work must dominate the
        # fixed dispatch + sync cost. Round 5 (VERDICT r4 #4): the
        # published number is the MEDIAN of three marginal estimates.
        import statistics

        params = train.init_params(jax.random.PRNGKey(0), cfg)
        tokens = jax.random.randint(jax.random.PRNGKey(1), (batch, S), 0,
                                    cfg.vocab)
        targets = jax.random.randint(jax.random.PRNGKey(2), (batch, S), 0,
                                     cfg.vocab)

        @functools.partial(jax.jit, static_argnames=("n",))
        def steps(params, tokens, n: int):
            def body(i, p):
                loss, grads = jax.value_and_grad(train.loss_fn)(
                    p, (tokens, targets), cfg)
                return jax.tree_util.tree_map(
                    lambda a, g: (a - 1e-4 * g).astype(a.dtype), p, grads)

            return jax.lax.fori_loop(0, n, body, params)

        def run(n):
            out = steps(params, tokens, n)
            jax.device_get(jax.tree.leaves(out)[0][:1])  # dependent fetch

        sec = statistics.median(_marginal(run, 1, hi) for _ in range(3))
        matmul_params = (cfg.n_layers * (cfg.d_model * 3 * cfg.d_model
                                         + cfg.d_model * cfg.d_model
                                         + 2 * cfg.d_model * cfg.d_ff)
                         + cfg.vocab * cfg.d_model)
        attn_flops = cfg.n_layers * 12 * S * S * cfg.d_model
        flops = 6.0 * matmul_params * batch * S + attn_flops * batch
        tf = flops / sec / 1e12
        return sec, tf, tf * 1e12 / peak["bf16_flops"]

    base = dict(vocab=16384, d_model=1024, n_heads=8, n_layers=8,
                d_ff=4096, max_seq=1024, dtype=jnp.bfloat16)
    cfg_on = train.ModelConfig(**base, use_flash_attention=True,
                               use_pallas_norm=True, use_fused_xent=True)
    cfg_off = train.ModelConfig(**base, use_flash_attention=False,
                                use_pallas_norm=False,
                                use_fused_xent=False)
    sec, tf, mfu = measure(cfg_on)
    print(f"# train step d_model=1024 L=8 B={B} S={S} KERNELS-ON "
          f"(flash+norm+xent): {sec*1e3:.1f} ms/step, {tf:7.2f} TFLOP/s, "
          f"MFU={mfu*100:.1f}% (bf16 peak)", flush=True)
    sec0, tf0, mfu0 = measure(cfg_off)
    print(f"# train step d_model=1024 L=8 B={B} S={S} XLA baseline: "
          f"{sec0*1e3:.1f} ms/step, {tf0:7.2f} TFLOP/s, "
          f"MFU={mfu0*100:.1f}%", flush=True)
    # at-scale point: matmuls dominate at d_model=2048
    cfg_big = train.ModelConfig(vocab=32768, d_model=2048, n_heads=16,
                                n_layers=12, d_ff=8192, max_seq=1024,
                                dtype=jnp.bfloat16,
                                use_flash_attention=True,
                                use_pallas_norm=True, use_fused_xent=True)
    secb, tfb, mfub = measure(cfg_big, batch=4, hi=7)
    print(f"# train step d_model=2048 L=12 B=4 S={S} KERNELS-ON "
          f"(at-scale): {secb*1e3:.1f} ms/step, {tfb:7.2f} TFLOP/s, "
          f"MFU={mfub*100:.1f}%", flush=True)
    return mfu


def main():
    import jax

    from brpc_tpu.tpu.compile_cache import enable_compile_cache
    from brpc_tpu.tpu.mesh import describe_devices

    enable_compile_cache()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"# kernel bench needs a TPU; JAX reports platform="
              f"{dev.platform!r}", flush=True)
        return 1
    peak = chip_peak(dev.device_kind)
    print(f"# kernel bench on {describe_devices()} jax={jax.__version__} "
          f"(peaks: {peak['bf16_flops'] / 1e12:.0f} TFLOP/s bf16, "
          f"{peak['hbm_bytes_per_s'] / 1e9:.0f} GB/s HBM)", flush=True)
    benches = {"flash_attention": bench_flash_attention,
               "ring_path": bench_ring_path,
               "ring_hops": bench_ring_hops,
               "rmsnorm": bench_rmsnorm,
               "paged_decode": bench_paged_decode,
               "ssm_scan": bench_ssm_scan,
               "cca_mix": bench_cca_mix,
               "sambay_decode": bench_sambay_decode,
               "mla_paged_decode": bench_mla_paged_decode,
               "mla_chunk": bench_mla_chunk,
               "train_step_mfu": bench_train_step_mfu}
    for name in sys.argv[1:] or list(benches):   # all, or the named ones
        benches[name](peak)
    return 0


if __name__ == "__main__":
    sys.exit(main())
