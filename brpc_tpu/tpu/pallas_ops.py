"""Pallas kernels for the hot ops (see /opt/skills/guides/pallas_guide.md).

Round-1 set: fused RMSNorm (memory-bound; fusing the square/mean/scale into
one VMEM pass saves two HBM round-trips vs the naive composition). Kernels
run natively on TPU and in interpret mode on the CPU test substrate; both
paths share one numerics test against the jnp reference.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

EPS = 1e-6


def _on_tpu() -> bool:
    """The one place that picks compiled-vs-interpreted Pallas: a backend
    that fails to initialise raises here instead of answering "not a
    TPU" and silently selecting interpret=True."""
    return jax.default_backend() == "tpu"


def rmsnorm_reference(x, w, eps: float = EPS):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x.astype(jnp.float32) * jax.lax.rsqrt(var + eps) * w).astype(x.dtype)


def _rmsnorm_kernel(x_ref, w_ref, o_ref, *, eps: float):
    x = x_ref[:].astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    o_ref[:] = (x * jax.lax.rsqrt(var + eps) * w_ref[:].astype(jnp.float32)
                ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("eps", "interpret", "block_rows"))
def _rmsnorm_fwd_call(x, w, eps: float = EPS, interpret: bool = None,
                      block_rows: int = 256):
    """Fused RMSNorm over the last dim. x: [..., D], w: [D]."""
    from jax.experimental import pallas as pl

    if interpret is None:
        interpret = not _on_tpu()
    orig_shape = x.shape
    D = orig_shape[-1]
    x2 = x.reshape(-1, D)
    N = x2.shape[0]
    rows = min(block_rows, N)
    if N % rows != 0:  # pad rows to a clean grid
        pad = rows - N % rows
        x2 = jnp.pad(x2, ((0, pad), (0, 0)))
    grid = (x2.shape[0] // rows,)
    from jax.experimental.pallas import tpu as pltpu

    params = (None if interpret else pltpu.CompilerParams(
        dimension_semantics=("parallel",)))
    out = pl.pallas_call(
        functools.partial(_rmsnorm_kernel, eps=eps),
        out_shape=jax.ShapeDtypeStruct(x2.shape, x.dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((rows, D), lambda i: (i, 0)),
            pl.BlockSpec((D,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((rows, D), lambda i: (i, 0)),
        compiler_params=params,
        interpret=interpret,
        name="rmsnorm",
    )(x2, w)
    return out[:N].reshape(orig_shape)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _rmsnorm_diff(x, w, eps, interpret, block_rows):
    return _rmsnorm_fwd_call(x, w, eps, interpret, block_rows)


def _rmsnorm_diff_fwd(x, w, eps, interpret, block_rows):
    return _rmsnorm_fwd_call(x, w, eps, interpret, block_rows), (x, w)


def _rmsnorm_diff_bwd(eps, interpret, block_rows, res, g):
    # backward stays XLA (memory-bound elementwise + reductions that XLA
    # fuses into two passes); the kernel wins the forward
    x, w = res
    xf = x.astype(jnp.float32)
    gf = g.astype(jnp.float32)
    wf = w.astype(jnp.float32)
    d = x.shape[-1]
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    r = jax.lax.rsqrt(var + eps)
    gw = gf * wf
    dx = gw * r - xf * (r ** 3 / d) * jnp.sum(gw * xf, axis=-1,
                                              keepdims=True)
    dw = jnp.sum((gf * xf * r).reshape(-1, d), axis=0)
    return dx.astype(x.dtype), dw.astype(w.dtype)


_rmsnorm_diff.defvjp(_rmsnorm_diff_fwd, _rmsnorm_diff_bwd)


def rmsnorm(x, w, eps: float = EPS, interpret: bool = None,
            block_rows: int = 256):
    """Fused RMSNorm over the last dim, differentiable (custom VJP).
    x: [..., D], w: [D]."""
    return _rmsnorm_diff(x, w, eps, interpret, block_rows)


# ---------------------------------------------------------------------------
# Flash attention — tiled online-softmax attention (the canonical TPU
# kernel: never materializes the S x S score matrix; K/V stream through
# VMEM tiles while running max/denominator accumulators live in scratch
# persisted across the innermost grid dimension).
#
# Perf notes (VERDICT r3 #2): operands stay bf16 INTO the MXU
# (preferred_element_type=f32 accumulates in the MXU's f32 pipeline —
# casting inputs to f32 first would halve MXU throughput and double VMEM
# traffic); the probability tile is cast back to bf16 for the PV matmul;
# grid dims carry dimension_semantics so Mosaic double-buffers the K/V
# streams under the "arbitrary" innermost dim.
# ---------------------------------------------------------------------------
NEG_INF = -1e30


def _dot_f32(a, b, *, trans_a: bool = False, trans_b: bool = False):
    """MXU matmul keeping operand dtype (bf16 in -> f32 accumulate)."""
    ca = 0 if trans_a else 1
    cb = 1 if trans_b else 0
    return jax.lax.dot_general(
        a, b, (((ca,), (cb,)), ((), ())),
        preferred_element_type=jnp.float32)


def _causal_three_way(live, full, accumulate):
    """Three-way causal tile split (VERDICT r4 #1): tiles fully below the
    diagonal run the mask-free body, the diagonal band runs the masked
    body, tiles above the diagonal run nothing. `live`/`full` are traced
    scalars; `accumulate(masked)` instantiates the tile body."""
    import jax.experimental.pallas as pl

    @pl.when(full)
    def _():
        accumulate(False)

    @pl.when(jnp.logical_and(live, jnp.logical_not(full)))
    def _():
        accumulate(True)


def attention_reference(q, k, v, causal: bool = False):
    """O(S^2)-memory reference for numerics tests."""
    s = jnp.einsum("qd,kd->qk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) / jnp.sqrt(q.shape[-1])
    if causal:
        mask = jnp.tril(jnp.ones(s.shape, dtype=bool))
        s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("qk,kd->qd", p, v.astype(jnp.float32)).astype(q.dtype)


# ---------------------------------------------------------------------------
# Batched (B*H-grid) flash attention with a Pallas backward pass.
#
# The multi-head entry point is NOT a double-vmap of the single-head kernel:
# batch*heads form the outermost ("parallel") grid dimension of one
# pallas_call, so Mosaic pipelines K/V tile fetches across heads instead of
# fencing at every vmap boundary. The forward emits the per-row logsumexp
# (lse = m + log l) as a residual; the backward is the standard two-kernel
# flash backward (dQ with K-inner grid; dK/dV with Q-inner grid) that
# recomputes probability tiles from (q, k, lse) instead of storing them —
# O(S) memory, same as the forward. All matmuls keep bf16 operands on the
# MXU with f32 accumulation. Reference semantics (not implementation):
# /root/reference — no analog; this is the TPU-native hot path the way
# the reference's wait-free bthread path is its hot path.
# ---------------------------------------------------------------------------
def _flash_fwd_bhsd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                           m_scr, l_scr, acc_scr, *,
                           causal: bool, bq: int, bk: int, nk: int,
                           bn: int = 1):
    import jax.experimental.pallas as pl

    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    def _accumulate(masked: bool):
        # bn heads ride one grid step (static unroll): the per-step
        # pipeline overhead (~µs on this substrate, docs/round5-notes.md)
        # is amortized over bn tiles' worth of MXU work
        share = bn // k_ref.shape[0]   # query heads a K/V head of the block
        for j in range(bn):
            q = q_ref[j]
            k = k_ref[j // share]
            v = v_ref[j // share]
            scale = 1.0 / float(q.shape[-1]) ** 0.5
            s = _dot_f32(q, k, trans_b=True) * scale
            if masked:
                q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32,
                                                           (bq, bk), 0)
                k_pos = ki * bk + jax.lax.broadcasted_iota(jnp.int32,
                                                           (bq, bk), 1)
                s = jnp.where(q_pos >= k_pos, s, NEG_INF)
            m_prev = m_scr[j]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_scr[j] = l_scr[j] * alpha + jnp.sum(p, axis=-1, keepdims=True)
            acc_scr[j] = acc_scr[j] * alpha + _dot_f32(p.astype(v.dtype), v)
            m_scr[j] = m_new

    if causal:
        _causal_three_way(qi * bq + bq - 1 >= ki * bk,
                          qi * bq >= ki * bk + bk - 1,
                          _accumulate)
    else:
        _accumulate(False)

    @pl.when(ki == nk - 1)
    def _finish():
        for j in range(bn):
            l = l_scr[j]
            safe = jnp.where(l == 0.0, 1.0, l)
            o_ref[j] = (acc_scr[j] / safe).astype(o_ref.dtype)
            # fully-masked rows keep lse = NEG_INF (l == 0): the backward
            # kernels key their "row attended to nothing" guard off it
            lse_ref[j] = jnp.where(l == 0.0, NEG_INF,
                                   m_scr[j] + jnp.log(safe))


def _flash_dq_kernel(pos_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                     delta_ref, dq_ref, dq_scr, *,
                     causal: bool, bq: int, bk: int, nk: int):
    import jax.experimental.pallas as pl

    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    def _accumulate(masked: bool):
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        scale = 1.0 / float(q.shape[-1]) ** 0.5
        s = _dot_f32(q, k, trans_b=True) * scale
        if masked:
            q_pos = pos_ref[0, 0] + qi * bq + jax.lax.broadcasted_iota(
                jnp.int32, (bq, bk), 0)
            k_pos = pos_ref[0, 1] + ki * bk + jax.lax.broadcasted_iota(
                jnp.int32, (bq, bk), 1)
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        lse = lse_ref[0]                                   # [bq, 1]
        # lse == NEG_INF marks rows that attended to nothing (a whole-hop-
        # in-the-future ring block): their probabilities are identically 0
        p = jnp.where(lse > NEG_INF / 2, jnp.exp(s - lse), 0.0)
        dp = _dot_f32(do, v, trans_b=True)
        ds = p * (dp - delta_ref[0])
        dq_scr[:] = dq_scr[:] + _dot_f32(ds.astype(k.dtype), k) * scale

    if causal:
        # absolute positions: ring hops feed runtime offsets
        _causal_three_way(
            pos_ref[0, 0] + qi * bq + bq - 1 >= pos_ref[0, 1] + ki * bk,
            pos_ref[0, 0] + qi * bq >= pos_ref[0, 1] + ki * bk + bk - 1,
            _accumulate)
    else:
        _accumulate(False)

    @pl.when(ki == nk - 1)
    def _finish():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _flash_dkv_kernel(pos_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                      delta_ref, dk_ref, dv_ref, dk_scr, dv_scr, *,
                      causal: bool, bq: int, bk: int, nq: int):
    import jax.experimental.pallas as pl

    ki = pl.program_id(1)
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    def _accumulate(masked: bool):
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        scale = 1.0 / float(q.shape[-1]) ** 0.5
        s = _dot_f32(q, k, trans_b=True) * scale           # [bq, bk]
        if masked:
            q_pos = pos_ref[0, 0] + qi * bq + jax.lax.broadcasted_iota(
                jnp.int32, (bq, bk), 0)
            k_pos = pos_ref[0, 1] + ki * bk + jax.lax.broadcasted_iota(
                jnp.int32, (bq, bk), 1)
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        lse = lse_ref[0]                                   # [bq, 1]
        p = jnp.where(lse > NEG_INF / 2, jnp.exp(s - lse), 0.0)
        # contract over the q dim (trans_a): p^T @ do and ds^T @ q on the
        # MXU without materializing transposed tiles
        dv_scr[:] = dv_scr[:] + _dot_f32(p.astype(do.dtype), do,
                                         trans_a=True)
        dp = _dot_f32(do, v, trans_b=True)
        ds = p * (dp - delta_ref[0])
        dk_scr[:] = dk_scr[:] + _dot_f32(ds.astype(q.dtype), q,
                                         trans_a=True) * scale

    if causal:
        # absolute positions: ring hops feed runtime offsets
        _causal_three_way(
            pos_ref[0, 0] + qi * bq + bq - 1 >= pos_ref[0, 1] + ki * bk,
            pos_ref[0, 0] + qi * bq >= pos_ref[0, 1] + ki * bk + bk - 1,
            _accumulate)
    else:
        _accumulate(False)

    @pl.when(qi == nq - 1)
    def _finish():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _fit_block(s: int, want: int) -> int:
    """Largest divisor of s that is <= want (so default block sizes never
    reject a sequence length the r3 kernel accepted)."""
    b = min(want, s)
    while s % b:
        b -= 1
    return b


def _pick_blocks(sq, sk, block_q, block_k, interpret, causal=False):
    """Swept on a v5e (docs/round4-notes.md §1): causal peaks at 1024x1024
    (smaller k-tiles keep the block-granular skip tight), non-causal at
    512x2048 (deepest k-stream per q residency). The causal half was
    measured again in PR 40 through the folded grid's rows-first call
    (:func:`_rows_tiles`, float32, 16 heads of 128): the largest tile won at
    every length from 128 to 1536. Explicit block sizes are honored exactly
    (and rejected if they don't divide); defaults fall back to the largest
    dividing block."""
    if interpret:
        want_q, want_k = 128, 128
    elif causal:
        want_q, want_k = 1024, 1024
    else:
        want_q, want_k = 512, 2048
    bq = min(block_q, sq) if block_q else _fit_block(sq, want_q)
    bk = min(block_k, sk) if block_k else _fit_block(sk, want_k)
    if sq % bq or sk % bk:
        raise ValueError(f"seq lengths ({sq},{sk}) must divide blocks "
                         f"({bq},{bk})")
    return bq, bk


def _kv_block(n: int, n_kv: int, bn: int):
    """Grouped heads: query head ``i`` of ``n`` reads K/V head ``i // per``
    of ``n_kv`` (``per = n // n_kv`` query heads a K/V head; 1 is a K/V head
    of its own a query head). For blocks of ``bn`` query heads: how many K/V
    heads a block holds, and its index from the query block's (the query
    block's own where each holds whole groups, so ``per == 1`` is the
    ungrouped program)."""
    per = n // n_kv
    if n % n_kv or (bn % per and per % bn):
        raise ValueError(f"{n} query heads over {n_kv} K/V heads in blocks "
                         f"of {bn}: a block holds whole groups or lies in "
                         "one")
    if bn % per == 0:
        return bn // per, lambda bi: bi
    return 1, lambda bi: bi * bn // per


@functools.partial(jax.jit, static_argnames=("causal", "bq", "bk",
                                             "interpret", "bn"))
def _flash_fwd_bhsd(q, k, v, causal: bool, bq: int, bk: int,
                    interpret: bool, bn: int = 1):
    """Forward over [N, S, D] (N = B*H): returns (o [N,S,D], lse [N,S]).
    ``bn`` = heads per grid step (must divide N); >1 amortizes per-step
    pipeline overhead at the cost of bn x the VMEM working set. k, v may
    hold fewer heads (:func:`_kv_block`)."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n, sq, d = q.shape
    sk = k.shape[1]
    nq, nk = sq // bq, sk // bk
    if n % bn:
        raise ValueError(f"bn ({bn}) must divide batch*heads ({n})")
    kn, kb = _kv_block(n, k.shape[0], bn)
    kernel = functools.partial(_flash_fwd_bhsd_kernel, causal=causal,
                               bq=bq, bk=bk, nk=nk, bn=bn)
    params = (None if interpret else pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary")))
    return pl.pallas_call(
        kernel,
        grid=(n // bn, nq, nk),
        in_specs=[
            pl.BlockSpec((bn, bq, d), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((kn, bk, d), lambda b, qi, ki: (kb(b), ki, 0)),
            pl.BlockSpec((kn, bk, d), lambda b, qi, ki: (kb(b), ki, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bn, bq, d), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((bn, bq, 1), lambda b, qi, ki: (b, qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, sq, d), q.dtype),
            jax.ShapeDtypeStruct((n, sq, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bn, bq, 1), jnp.float32),
            pltpu.VMEM((bn, bq, 1), jnp.float32),
            pltpu.VMEM((bn, bq, d), jnp.float32),
        ],
        compiler_params=params,
        interpret=interpret,
        name="flash_fwd_bhsd",
    )(q, k, v)


# ---------------------------------------------------------------------------
# Folded (triangular) causal flash forward — round 5, VERDICT r4 #1.
#
# The (qi, ki) grid pays this substrate's ~1.2 µs/step pipeline overhead
# AND a K/V tile fetch even for skipped above-diagonal tiles. For causal
# with bq == bk the live tiles form the lower triangle, so this variant's
# grid IS the triangle: step t of nq*(nq+1)/2 maps to (qi, ki) with
# qi = row(t) (inverse triangular number, computed in the index maps),
# ki = t - qi*(qi+1)/2. No skipped steps, no wasted fetches; diagonal
# steps (ki == qi) run the masked body, interior steps run mask-free.
# bn heads share each step to amortize the fixed per-step cost.
# ---------------------------------------------------------------------------
def _tri_row(t):
    """Row of linear triangular index t (qi such that qi*(qi+1)/2 <= t <
    (qi+1)*(qi+2)/2), with integer fix-up of the f32 sqrt."""
    qi = ((jnp.sqrt(8.0 * t.astype(jnp.float32) + 1.0) - 1.0) / 2.0
          ).astype(jnp.int32)
    qi = jnp.where(qi * (qi + 1) // 2 > t, qi - 1, qi)
    qi = jnp.where((qi + 1) * (qi + 2) // 2 <= t, qi + 1, qi)
    return qi


def _flash_fwd_folded_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                             m_scr, l_scr, acc_scr, *,
                             b: int, bn: int, one_tile: bool = False):
    import jax.experimental.pallas as pl

    if one_tile:
        # the whole sequence is ONE tile a head: the triangle is its
        # diagonal step, so nothing is looked up and `pl.when` on a plain
        # bool keeps or drops a body while tracing (half the program to
        # trace and lower)
        qi = ki = 0
    else:
        t = pl.program_id(1)
        qi = _tri_row(t)
        ki = t - qi * (qi + 1) // 2
    # a block is (bn, b, d), heads first, or (b, bn * d), the rows as a
    # projection made them: head j is then a lane-aligned column block
    d = acc_scr.shape[-1]
    rows_first = len(q_ref.shape) == 2

    def tile(ref, j):
        return ref[:, j * d:(j + 1) * d] if rows_first else ref[j]

    @pl.when(ki == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    def _update(j, s, v):
        """Online-softmax update of head j's scratch with scores s."""
        m_prev = m_scr[j]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[j] = l_scr[j] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[j] = acc_scr[j] * alpha + _dot_f32(p.astype(v.dtype), v)
        m_scr[j] = m_new

    def _accumulate(masked: bool):
        # query heads a K/V head of the block (rows first: one)
        share = 1 if rows_first else bn // k_ref.shape[0]
        for j in range(bn):
            q = tile(q_ref, j)
            k = tile(k_ref, j // share)
            v = tile(v_ref, j // share)
            scale = 1.0 / float(q.shape[-1]) ** 0.5
            s = _dot_f32(q, k, trans_b=True) * scale
            if masked:
                # on-diagonal tile: triangular mask with RELATIVE
                # positions (qi*b + r >= ki*b + c, qi == ki -> r >= c)
                r_pos = jax.lax.broadcasted_iota(jnp.int32, (b, b), 0)
                c_pos = jax.lax.broadcasted_iota(jnp.int32, (b, b), 1)
                s = jnp.where(r_pos >= c_pos, s, NEG_INF)
            _update(j, s, v)

    @pl.when(ki != qi)
    def _():
        _accumulate(False)

    @pl.when(ki == qi)
    def _():
        _accumulate(True)

    @pl.when(ki == qi)  # last visit of this q-tile: normalize + write
    def _finish():
        for j in range(bn):
            l = l_scr[j]
            safe = jnp.where(l == 0.0, 1.0, l)
            out = (acc_scr[j] / safe).astype(o_ref.dtype)
            if rows_first:
                o_ref[:, j * d:(j + 1) * d] = out
            else:
                o_ref[j] = out
            lse_ref[j] = jnp.where(l == 0.0, NEG_INF,
                                   m_scr[j] + jnp.log(safe))


@functools.partial(jax.jit, static_argnames=("b", "interpret", "bn"))
def _flash_fwd_folded(q, k, v, b: int, interpret: bool, bn: int = 1):
    """Causal forward over [N, S, D] via the triangular grid; bq = bk = b.
    Returns (o, lse). Causal masking uses absolute positions aligned at 0
    (the non-ring case); ring hops keep the (qi, ki) kernels. k, v may hold
    fewer heads (:func:`_kv_block`)."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n, sq, d = q.shape
    sk = k.shape[1]
    if sq != sk:
        raise ValueError("folded causal kernel requires sq == sk")
    if n % bn or sq % b:
        raise ValueError(f"shape ({n},{sq}) vs blocks (bn={bn},b={b})")
    kn, kb = _kv_block(n, k.shape[0], bn)
    nq = sq // b
    steps = nq * (nq + 1) // 2
    kernel = functools.partial(_flash_fwd_folded_kernel, b=b, bn=bn)
    params = (None if interpret else pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary")))

    def qmap(bi, t):
        return (bi, _tri_row(t), 0)

    def kmap(bi, t):
        qi = _tri_row(t)
        return (kb(bi), t - qi * (qi + 1) // 2, 0)

    return pl.pallas_call(
        kernel,
        grid=(n // bn, steps),
        in_specs=[
            pl.BlockSpec((bn, b, d), qmap),
            pl.BlockSpec((kn, b, d), kmap),
            pl.BlockSpec((kn, b, d), kmap),
        ],
        out_specs=[
            pl.BlockSpec((bn, b, d), qmap),
            pl.BlockSpec((bn, b, 1), qmap),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, sq, d), q.dtype),
            jax.ShapeDtypeStruct((n, sq, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bn, b, 1), jnp.float32),
            pltpu.VMEM((bn, b, 1), jnp.float32),
            pltpu.VMEM((bn, b, d), jnp.float32),
        ],
        compiler_params=params,
        interpret=interpret,
        name="flash_fwd_folded",
    )(q, k, v)


# The rows-first call of the folded forward: one sequence whose q, k, v lie
# as a projection made them, (S, heads * d), a head a column block of d
# lanes. A BlockSpec of (b, bn * d) columns reads a head where it lies and
# writes the output as the next projection contracts it, so no caller
# splits or transposes (at d = 128 the heads-first call costs seven copies
# a layer around the kernel). Swept on a v5e (PR 40: 16 heads of 128,
# float32, every 128th length to 1536, tiles of 128 .. S, 1 .. 16 heads a
# step): the LARGEST tile won at every length, a one-tile 1024 at 78 us
# against 117 us for tiles of 512. Heads share a step only where a tile is
# small: at 128 rows four heads a step take 9 us where one takes 15, from
# 512 rows on a second head gained 0-8% of the kernel, and every head more
# is a copy more of the body to trace and lower in every bucket's program
# (~0.1 s each on the chip's host, which `setup_s` pays). Mosaic's default
# product of float32 tiles is ONE bfloat16 pass summed in float32 (the same
# sweep: to the last digit the output of explicitly rounded operands),
# which is the precision an XLA matmul has on the TPU.
_ROWS_VMEM_LIMIT = 64 << 20   # scoped, of a v5e's 128 MiB
_ROWS_VMEM_TILES = 40 << 20   # what _rows_tiles lets its own estimate reach
_ROWS_A_STEP = 512            # rows of q a grid step, over its heads ...
_ROWS_HEADS = 4               # ... and the most heads


def _rows_tiles(s: int, n_heads: int, d: int, itemsize: int,
                heads_at=(0, 0, 0)):
    """(b, bn) of a rows-first call from the shape and the operands' bytes:
    ``b`` the largest tile that divides ``s`` (``s`` itself, or a multiple
    of 128 lanes for the (b, b) scores) whose working set fits, ``bn`` the
    most heads a step (a power of two that divides ``n_heads`` and every
    offset of ``heads_at``) within _ROWS_A_STEP rows and _ROWS_HEADS."""
    def fits(b):
        # scores and probabilities in float32 once; a head's q, k, v, o
        # tiles held twice (the pipeline), its accumulator, and its (b, 1)
        # statistics and lse blocks padded to a lane tile each
        return (8 * b * b + b * (8 * d * itemsize + 4 * d + 4 * 512)
                <= _ROWS_VMEM_TILES)

    tiles = [s] + [b for b in range(s - s % 128, 0, -128) if s % b == 0]
    b = next((b for b in tiles if fits(b)), tiles[-1])
    bn = 1
    while (2 * bn <= _ROWS_HEADS and 2 * bn * b <= _ROWS_A_STEP
           and not any(x % (2 * bn) for x in (n_heads, *heads_at))):
        bn *= 2
    return b, bn


@functools.partial(jax.jit, static_argnames=("n_heads", "head_dim",
                                             "heads_at", "interpret"))
def flash_attention_rows(q, k, v, n_heads: int, head_dim: int,
                         heads_at=(0, 0, 0), interpret: bool = None):
    """Causal self-attention of ONE sequence in one call of the folded
    forward, rows first. q, k, v: (S, >= n_heads * head_dim); head ``h`` of
    q lies in columns ``(heads_at[0] + h) * head_dim ...``, of k and v from
    ``heads_at[1]`` and ``heads_at[2]``, so the packed (S, 3 H d) output of
    one projection is handed in three times and never split. Returns
    (S, n_heads * head_dim) in q's dtype. The forward only. Where a head is
    no lane-aligned column block (``head_dim % 128`` on a TPU) the heads go
    first through copies into :func:`flash_attention_mha`."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if interpret is None:
        interpret = not _on_tpu()
    s, d = q.shape[0], head_dim
    if not interpret and d % 128:
        qh, kh, vh = (
            x[:, at * d:(at + n_heads) * d].reshape(s, n_heads, d)
            .transpose(1, 0, 2)[None] for x, at in zip((q, k, v), heads_at))
        out = flash_attention_mha(qh, kh, vh, causal=True, interpret=False)
        return out[0].transpose(1, 0, 2).reshape(s, n_heads * d)
    b, bn = _rows_tiles(s, n_heads, d, q.dtype.itemsize, heads_at)
    nq = s // b
    q_at, k_at, v_at = (at // bn for at in heads_at)

    def q_tile(t):   # of triangle step t
        return 0 if nq == 1 else _tri_row(t)

    def rows(at):
        return lambda bi, t: (q_tile(t), at + bi)

    def keys(at):
        def index(bi, t):
            qi = q_tile(t)
            return (t - qi * (qi + 1) // 2, at + bi)
        return index

    params = (None if interpret else pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"),
        vmem_limit_bytes=_ROWS_VMEM_LIMIT))
    out, _ = pl.pallas_call(
        functools.partial(_flash_fwd_folded_kernel, b=b, bn=bn,
                          one_tile=nq == 1),
        grid=(n_heads // bn, nq * (nq + 1) // 2),
        in_specs=[
            pl.BlockSpec((b, bn * d), rows(q_at)),
            pl.BlockSpec((b, bn * d), keys(k_at)),
            pl.BlockSpec((b, bn * d), keys(v_at)),
        ],
        out_specs=[
            pl.BlockSpec((b, bn * d), rows(0)),
            pl.BlockSpec((bn, b, 1), lambda bi, t: (bi, q_tile(t), 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((s, n_heads * d), q.dtype),
            jax.ShapeDtypeStruct((n_heads, s, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bn, b, 1), jnp.float32),
            pltpu.VMEM((bn, b, 1), jnp.float32),
            pltpu.VMEM((bn, b, d), jnp.float32),
        ],
        compiler_params=params,
        interpret=interpret,
        name="flash_attention_rows",
    )(q, k, v)
    return out


def _flash_delta(o, do):
    """delta = rowsum(dO * O) — loop-invariant in the ring backward, so
    it is computed ONCE by the caller, not per hop."""
    return jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                   axis=-1, keepdims=True)                # [N, sq, 1]


@functools.partial(jax.jit, static_argnames=("causal", "bq", "bk",
                                             "interpret", "vma"))
def _flash_bwd_bhsd(q, k, v, lse, do, delta, q_start, k_start,
                    causal: bool, bq: int, bk: int, interpret: bool,
                    vma=None):
    """Backward over [N, S, D]: returns (dq, dk, dv). q_start/k_start are
    absolute sequence offsets (traced scalars) so the ring backward can
    reuse these kernels per hop with causal masking intact. ``vma``:
    varying mesh axes when called inside a shard_map."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    vset = set(vma) if vma else None

    n, sq, d = q.shape
    sk = k.shape[1]
    nq, nk = sq // bq, sk // bk
    pos = jnp.stack([jnp.asarray(q_start, jnp.int32),
                     jnp.asarray(k_start, jnp.int32)])[None, :]
    params = (None if interpret else pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary")))

    dq = pl.pallas_call(
        functools.partial(_flash_dq_kernel, causal=causal, bq=bq, bk=bk,
                          nk=nk),
        grid=(n, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 2), lambda b, qi, ki: (0, 0)),
            pl.BlockSpec((1, bq, d), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, bk, d), lambda b, qi, ki: (b, ki, 0)),
            pl.BlockSpec((1, bk, d), lambda b, qi, ki: (b, ki, 0)),
            pl.BlockSpec((1, bq, d), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, bq, 1), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, bq, 1), lambda b, qi, ki: (b, qi, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda b, qi, ki: (b, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((n, sq, d), q.dtype, vma=vset),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        compiler_params=params,
        interpret=interpret,
        name="flash_dq",
    )(pos, q, k, v, do, lse, delta)

    dk, dv = pl.pallas_call(
        functools.partial(_flash_dkv_kernel, causal=causal, bq=bq, bk=bk,
                          nq=nq),
        grid=(n, nk, nq),
        in_specs=[
            pl.BlockSpec((1, 2), lambda b, ki, qi: (0, 0)),
            pl.BlockSpec((1, bq, d), lambda b, ki, qi: (b, qi, 0)),
            pl.BlockSpec((1, bk, d), lambda b, ki, qi: (b, ki, 0)),
            pl.BlockSpec((1, bk, d), lambda b, ki, qi: (b, ki, 0)),
            pl.BlockSpec((1, bq, d), lambda b, ki, qi: (b, qi, 0)),
            pl.BlockSpec((1, bq, 1), lambda b, ki, qi: (b, qi, 0)),
            pl.BlockSpec((1, bq, 1), lambda b, ki, qi: (b, qi, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bk, d), lambda b, ki, qi: (b, ki, 0)),
            pl.BlockSpec((1, bk, d), lambda b, ki, qi: (b, ki, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, sk, d), k.dtype, vma=vset),
            jax.ShapeDtypeStruct((n, sk, d), v.dtype, vma=vset),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, d), jnp.float32),
            pltpu.VMEM((bk, d), jnp.float32),
        ],
        compiler_params=params,
        interpret=interpret,
        name="flash_dkv",
    )(pos, q, k, v, do, lse, delta)
    return dq, dk, dv


def _flash_fwd_best(q, k, v, causal, bq, bk, interpret):
    """Forward dispatch: causal self-attention takes the folded
    triangular grid (no skipped steps); everything else takes the (qi, ki)
    grid with bn=2 heads per step when the batch divides."""
    n = q.shape[0]
    if causal and bq == bk and q.shape[1] == k.shape[1]:
        return _flash_fwd_folded(q, k, v, bq, interpret)
    # bn=2 at bq=1024 exceeds the 16MB VMEM scoped limit (sweep FAILs);
    # two heads a step read one K/V head or two whole groups' (_kv_block)
    per = n // k.shape[0]
    bn = 2 if n % 2 == 0 and bq <= 512 and (per == 1 or per % 2 == 0) else 1
    return _flash_fwd_bhsd(q, k, v, causal, bq, bk, interpret, bn)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_mha_diff(q, k, v, causal, bq, bk, interpret):
    o, _ = _flash_fwd_best(q, k, v, causal, bq, bk, interpret)
    return o


def _flash_mha_diff_fwd(q, k, v, causal, bq, bk, interpret):
    o, lse = _flash_fwd_best(q, k, v, causal, bq, bk, interpret)
    return o, (q, k, v, o, lse)


def _flash_mha_diff_bwd(causal, bq, bk, interpret, res, do):
    q, k, v, o, lse = res
    dq, dk, dv = _flash_bwd_bhsd(q, k, v, lse, do, _flash_delta(o, do),
                                 0, 0, causal, bq, bk, interpret)
    return dq, dk, dv


_flash_mha_diff.defvjp(_flash_mha_diff_fwd, _flash_mha_diff_bwd)


def flash_attention_mha(q, k, v, causal: bool = False, block_q: int = None,
                        block_k: int = None, interpret: bool = None):
    """(B, H, S, D) multi-head flash attention — one pallas_call with a
    (B*H, q-tiles, k-tiles) grid, differentiable via the Pallas backward
    kernels above. k, v (B, G, S, D) with G dividing H: query head ``h``
    reads K/V head ``h // (H / G)`` in place, nothing repeated; the forward
    only (the backward kernels take a K/V head a query head)."""
    if interpret is None:
        interpret = not _on_tpu()
    b, h, sq, d = q.shape
    g, sk = k.shape[1], k.shape[2]
    bq, bk = _pick_blocks(sq, sk, block_q, block_k, interpret, causal)
    fwd = _flash_mha_diff if g == h else (
        lambda *args: _flash_fwd_best(*args)[0])
    o = fwd(q.reshape(b * h, sq, d), k.reshape(b * g, sk, d),
            v.reshape(b * g, sk, d), causal, bq, bk, interpret)
    return o.reshape(b, h, sq, d)


# ---------------------------------------------------------------------------
# Carry-form flash attention — the ring-attention inner kernel (VERDICT r2
# #5): instead of normalizing at the end, the running (m, l, acc) online-
# softmax state enters as inputs and leaves as outputs, so hops of a KV
# ring accumulate through the SAME kernel; the ring normalizes once after
# the last hop. Causal masking uses ABSOLUTE positions fed at runtime
# (each hop's KV block originated on a different device).
# ---------------------------------------------------------------------------
def _flash_carry_kernel(pos_ref, q_ref, k_ref, v_ref, m_in, l_in, acc_in,
                        m_out, l_out, acc_out, m_scr, l_scr, acc_scr, *,
                        causal: bool, bq: int, bk: int, nk: int):
    import jax.experimental.pallas as pl

    qi = pl.program_id(0)
    ki = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        m_scr[:] = m_in[:]
        l_scr[:] = l_in[:]
        acc_scr[:] = acc_in[:]

    def _accumulate(masked: bool):
        q = q_ref[:]
        k = k_ref[:]
        v = v_ref[:]
        scale = 1.0 / float(q.shape[-1]) ** 0.5
        s = _dot_f32(q, k, trans_b=True) * scale
        if masked:
            q_pos = pos_ref[0, 0] + qi * bq + jax.lax.broadcasted_iota(
                jnp.int32, (bq, bk), 0)
            k_pos = pos_ref[0, 1] + ki * bk + jax.lax.broadcasted_iota(
                jnp.int32, (bq, bk), 1)
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        m_prev = m_scr[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        if masked:
            # rows that have seen nothing but masked scores (whole-hop-in-
            # the-future blocks) must stay at the identity, not
            # exp(-inf - -inf) = 1
            alive = m_new > NEG_INF / 2
            p = jnp.where(alive, jnp.exp(s - m_new), 0.0)
            alpha = jnp.where(alive, jnp.exp(m_prev - m_new), 0.0)
        else:
            # unmasked tile: m_new is finite, and exp(m_prev - m_new)
            # underflows to the correct 0 when m_prev is the NEG_INF
            # "seen nothing yet" sentinel
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
        l_scr[:] = l_scr[:] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[:] = acc_scr[:] * alpha + _dot_f32(p.astype(v.dtype), v)
        m_scr[:] = m_new

    if causal:
        # absolute positions: ring hops feed runtime offsets
        _causal_three_way(
            pos_ref[0, 0] + qi * bq + bq - 1 >= pos_ref[0, 1] + ki * bk,
            pos_ref[0, 0] + qi * bq >= pos_ref[0, 1] + ki * bk + bk - 1,
            _accumulate)
    else:
        _accumulate(False)

    @pl.when(ki == nk - 1)
    def _finish():
        m_out[:] = m_scr[:]
        l_out[:] = l_scr[:]
        acc_out[:] = acc_scr[:]


@functools.partial(jax.jit,
                   static_argnames=("causal", "block_q", "block_k",
                                    "interpret", "vma"))
def flash_attention_carry(q, k, v, m, l, acc, q_start, k_start,
                          causal: bool = False, block_q: int = 128,
                          block_k: int = 128, interpret: bool = None,
                          vma=None):
    """One online-softmax accumulation pass over (k, v) for queries q,
    continuing running state. q: [sq, D]; k,v: [sk, D]; m, l: [sq, 1]
    float32; acc: [sq, D] float32; q_start/k_start: absolute sequence
    offsets (traced scalars) for causal masking. Returns (m', l', acc').
    Normalize with acc/l after the final pass. ``vma``: varying mesh axes
    when called inside a shard_map (ring attention passes its sharded
    axes so shard_map's varying-axes checker can type the outputs)."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if interpret is None:
        interpret = not _on_tpu()
    sq, d = q.shape
    sk = k.shape[0]
    bq = min(block_q, sq)
    bk = min(block_k, sk)
    if sq % bq or sk % bk:
        raise ValueError(f"seq lengths ({sq},{sk}) must divide blocks "
                         f"({bq},{bk})")
    nq, nk = sq // bq, sk // bk
    pos = jnp.stack([jnp.asarray(q_start, jnp.int32),
                     jnp.asarray(k_start, jnp.int32)])[None, :]
    kernel = functools.partial(_flash_carry_kernel, causal=causal, bq=bq,
                               bk=bk, nk=nk)
    vset = set(vma) if vma else None
    return pl.pallas_call(
        kernel,
        grid=(nq, nk),
        in_specs=[
            pl.BlockSpec((1, 2), lambda qi, ki: (0, 0)),
            pl.BlockSpec((bq, d), lambda qi, ki: (qi, 0)),
            pl.BlockSpec((bk, d), lambda qi, ki: (ki, 0)),
            pl.BlockSpec((bk, d), lambda qi, ki: (ki, 0)),
            pl.BlockSpec((bq, 1), lambda qi, ki: (qi, 0)),
            pl.BlockSpec((bq, 1), lambda qi, ki: (qi, 0)),
            pl.BlockSpec((bq, d), lambda qi, ki: (qi, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bq, 1), lambda qi, ki: (qi, 0)),
            pl.BlockSpec((bq, 1), lambda qi, ki: (qi, 0)),
            pl.BlockSpec((bq, d), lambda qi, ki: (qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((sq, 1), jnp.float32, vma=vset),
            jax.ShapeDtypeStruct((sq, 1), jnp.float32, vma=vset),
            jax.ShapeDtypeStruct((sq, d), jnp.float32, vma=vset),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        interpret=interpret,
        name="flash_carry",
    )(pos, q, k, v, m, l, acc)


# ---------------------------------------------------------------------------
# Paged decode attention — one query row per sequence against the K/V pages
# where they lie in the pools. The block table and the rows' lengths are
# scalar-prefetched; each grid step takes `pages` pages of one row, every
# page through a BlockSpec of its own over the SAME pool operand whose
# index_map reads the table, so Mosaic's pipeline fetches a page straight
# from the pool and prefetches the next row's first pages behind the
# current row's last. A step takes 128 positions (the MXU's tile of keys),
# 8 pages at block 16, at any row width: each page is a BlockSpec lowered
# into every program that holds the kernel, and 32 of them a step (512
# positions at bfloat16 rows of 1024) tripled the kernel's lowering, which
# the host redoes at every set-up (PERF.md section 6). A page past the
# row's length keeps the index it had one step earlier: the pipeline
# fetches nothing for an unchanged index, and `pl.when` skips the chunk
# (~1 us a step on a v5e: PERF.md section 5, `chat-steady`).
# ``fetch_plan``: the grid is the rows' live steps alone, one dimension
# whose size is computed on the device: a plan of (row, chunk) pairs,
# scalar-prefetched beside the table, a row's chunks in order. A caller
# whose table spans the whole served context (8192 positions, 64 steps a
# row) walks a short row's few steps and no others.
# A table's last chunk may be ragged: its columns past the table repeat
# an earlier page of the row, past the row's length, masked.
#
# Heads. With a key/value head a query head, the scores of all heads come
# from ONE MXU product against the block-diagonal query (H, D) (one
# cross-lane reduction a head and position otherwise); the P V product
# yields (H, D) whose diagonal blocks are the heads' outputs. With grouped
# heads (H query heads over G key/value heads), each key/value head's
# group of H / G query heads is one product against that head's columns
# of the chunk, (H / G, hd) x (hd, T), and (H / G, T) x (T, hd) after it.
#
# A ring (``ring=(ring_rows, window)``): the table is a window layer's
# ring, ring row r holding the newest position p <= pos with p = r (mod
# ring_rows) (K was rotated when written, so where a row lies in the ring
# is nothing to the softmax). A row at ``pos = length - 1`` fetches ring
# rows 0 .. min(length, ring_rows) - 1 and masks those whose position lies
# at or below pos - window.
#
# Matmul operands are rounded as the backend's default precision rounds the
# gather body's einsums: to bfloat16 on the TPU (one MXU pass), not at all
# in the interpreted kernel on the CPU; sums and the online softmax are
# float32 everywhere.
# ---------------------------------------------------------------------------
_PAGED_CHUNK = 128   # positions a grid step: the MXU's tile of keys
# the scalar-prefetched block table (rows x pages, int32) lives in SMEM,
# 1 MiB on a v5e with the kernel's own scalars beside it
PAGED_TABLE_BYTES = 512 << 10
# a caller's tables come in multiples of this many pages (2048 positions at
# block 16): a dead column costs one skipped grid step a chunk (~1 us),
# a table width of its own costs a compiled program
PAGED_TABLE_PAGES = 128


def paged_table_pages(n_pages: int) -> int:
    """The width of the block table a caller hands the kernel for rows of
    up to ``n_pages`` pages."""
    return -(-n_pages // PAGED_TABLE_PAGES) * PAGED_TABLE_PAGES


def _paged_decode_kernel(layer_ref, table_ref, len_ref, *refs, pages: int,
                         bs: int, n_heads: int, nj: int, group: int, ring,
                         plan: bool):
    import jax.experimental.pallas as pl

    del layer_ref, table_ref   # read by the index maps
    if plan:
        row_ref, chunk_ref, q_ref, *refs = refs
        b = row_ref[pl.program_id(0)]
        j = chunk_ref[pl.program_id(0)]
    else:
        q_ref, *refs = refs
        b = pl.program_id(0)
        j = pl.program_id(1)
    k_refs, v_refs = refs[:pages], refs[pages:2 * pages]
    o_ref, kbuf, vbuf, m_scr, l_scr, acc_scr = refs[2 * pages:]
    length = len_ref[b]
    H, D = acc_scr.shape     # grouped heads: D is one head's width
    hd = D // n_heads if group == 1 else D
    T = pages * bs
    if group == 1:
        own = (jax.lax.broadcasted_iota(jnp.int32, (H, D), 1) // hd
               == jax.lax.broadcasted_iota(jnp.int32, (H, D), 0))
    # the positions (ring rows) the row reads
    fetch = length if ring is None else jnp.minimum(length, ring[0])

    def live(rows):
        """(rows, T): the positions of this chunk the row attends to."""
        r = j * T + jax.lax.broadcasted_iota(jnp.int32, (rows, T), 1)
        ok = r < fetch
        if ring is not None:
            n, window = ring
            # ring row r holds position pos - ((pos - r) mod n)
            age = jax.lax.rem(length - 1, n) - r
            ok = ok & (jnp.where(age < 0, age + n, age) < window)
        return ok

    def update(heads, s, cols):
        """The online softmax of query rows ``heads`` over the chunk's
        scores ``s``, against V's columns ``cols``. A chunk with no live
        position (a ring's, under a window much narrower than the ring)
        leaves m at NEG_INF and counts in l and acc that the first live
        chunk scales by exp(NEG_INF - m) = 0; after a live chunk its
        scores' exp underflow to the correct 0 (without a ring the first
        chunk always holds position 0)."""
        m_prev = m_scr[heads]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[heads] = l_scr[heads] * alpha + jnp.sum(p, axis=-1,
                                                      keepdims=True)
        acc_scr[heads] = acc_scr[heads] * alpha + _dot_f32(
            p.astype(vbuf.dtype), vbuf[:, cols])
        m_scr[heads] = m_new

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    @pl.when(j * T < fetch)
    def _chunk():
        for i in range(pages):
            kbuf[i * bs:(i + 1) * bs, :] = k_refs[i][:].astype(kbuf.dtype)
            vbuf[i * bs:(i + 1) * bs, :] = v_refs[i][:].astype(vbuf.dtype)
        every = slice(None)
        if group == 1:
            q = jnp.broadcast_to(q_ref[0], (H, D))
            qbd = jnp.where(own, q, 0.0).astype(kbuf.dtype)
            s = _dot_f32(qbd, kbuf[:], trans_b=True) / float(hd) ** 0.5
            update(every, jnp.where(live(H), s, NEG_INF), every)
            return
        ok = live(group)
        for g in range(n_heads // group):
            heads = slice(g * group, (g + 1) * group)
            cols = slice(g * hd, (g + 1) * hd)
            q = q_ref[0, heads, :].astype(kbuf.dtype)
            s = _dot_f32(q, kbuf[:, cols], trans_b=True) / float(hd) ** 0.5
            update(heads, jnp.where(ok, s, NEG_INF), cols)

    # a plan's row ends at its last live chunk, a grid's at the table's end
    @pl.when((j + 1) * T >= fetch if plan else j == nj - 1)
    def _finish():
        out = acc_scr[:] / l_scr[:]
        if group == 1:
            out = jnp.sum(jnp.where(own, out, 0.0), axis=0, keepdims=True)
        o_ref[0] = out.astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("n_heads", "block_size", "interpret",
                                    "operand_dtype", "n_kv_heads", "ring",
                                    "fetch_plan"))
def paged_decode_attention(q, k_pool, v_pool, layer, block_tables, lengths,
                           n_heads: int, block_size: int,
                           interpret: bool = None, operand_dtype=None,
                           n_kv_heads: int = None, ring=None,
                           fetch_plan: bool = False):
    """Attention of ONE query row per sequence over its own paged prefix.

    q: (B, n_heads * head_dim), heads side by side; k_pool, v_pool: the
    WHOLE pools, (layers, slots, n_kv_heads * head_dim), a page being
    ``block_size`` consecutive slots; query head h reads key/value head
    ``h // (n_heads / n_kv_heads)`` (``n_kv_heads``: ``n_heads`` where it
    is None); layer: int32 scalar (an operand, so every layer of a program
    runs the same kernel); block_tables: (B, pages) int32 physical block
    ids; lengths: (B,) int32, each >= 1, the positions ``0 .. length - 1``
    a row attends to (pages past it are neither fetched nor computed).
    ``ring``: None, or ``(ring_rows, window)`` where the tables are window
    rings (see above): a row fetches ``min(length, ring_rows)`` ring rows
    and attends to the positions ``length - window .. length - 1`` among
    them. ``fetch_plan``: walk the rows' live steps alone (see above), not
    every column of the table. ``operand_dtype``: what the two products'
    operands are rounded to; by default the backend's own default
    precision (see above). Returns (B, n_heads * head_dim) in q's dtype."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if interpret is None:
        interpret = not _on_tpu()
    if operand_dtype is None:
        operand_dtype = jnp.float32 if interpret else jnp.bfloat16
    B, Dq = q.shape
    n_layers, slots, D = k_pool.shape
    kv_heads = n_kv_heads or n_heads
    group, hd = n_heads // kv_heads, Dq // n_heads
    if n_heads % kv_heads or D != kv_heads * hd:
        raise ValueError(f"{n_heads} query heads of {hd} over pools of "
                         f"{D}: not {kv_heads} key/value heads")
    bs = block_size
    n_pages = block_tables.shape[1]
    pages = min(max(1, _PAGED_CHUNK // bs), n_pages)
    nj = -(-n_pages // pages)
    # the table and a plan's two columns of up to B * nj steps, in SMEM
    if (block_tables.size + (2 * B * nj if fetch_plan else 0)) * 4 \
            > PAGED_TABLE_BYTES:
        raise ValueError(f"a table of {B} x {n_pages} pages does not fit "
                         f"the kernel's {PAGED_TABLE_BYTES} bytes of SMEM")
    lengths = lengths.astype(jnp.int32)
    # (layers, blocks, bs, D): a bitcast where bs is a multiple of the
    # sublane tile (8 float32 rows, 16 bfloat16)
    k4 = k_pool.reshape(n_layers, slots // bs, bs, D)
    v4 = v_pool.reshape(n_layers, slots // bs, bs, D)

    def step(*ids):
        """(row, chunk, layer_ref, table_ref, len_ref) of a grid step."""
        if not fetch_plan:
            return ids
        i, layer_ref, table_ref, len_ref, row_ref, chunk_ref = ids
        return row_ref[i], chunk_ref[i], layer_ref, table_ref, len_ref

    def page_spec(i):
        def index(*ids):
            b, j, layer_ref, table_ref, len_ref = step(*ids)
            n = len_ref[b] if ring is None \
                else jnp.minimum(len_ref[b], ring[0])
            live = (n + bs - 1) // bs
            # the last step at which page j * pages + i is live; later
            # steps repeat its index so nothing is fetched for them
            last = jnp.maximum(live - 1 - i, 0) // pages
            page = jnp.minimum(j, last) * pages + i
            return (layer_ref[0], table_ref[b * n_pages + page], 0, 0)
        return pl.BlockSpec((None, None, bs, D), index)

    def row(*ids):
        return (step(*ids)[0], 0, 0)

    # a key/value head a query head: the row as it is, heads side by side
    # (the block-diagonal form); grouped: a query head a sublane row
    width = (1, Dq) if group == 1 else (n_heads, hd)
    kernel = functools.partial(_paged_decode_kernel, pages=pages, bs=bs,
                               n_heads=n_heads, nj=nj, group=group,
                               ring=ring, plan=fetch_plan)
    scalars = [jnp.asarray(layer, jnp.int32).reshape(1),
               block_tables.astype(jnp.int32).reshape(-1), lengths]
    if fetch_plan:
        # step i of the plan: row b's chunk c, a row's chunks in order
        fetch = lengths if ring is None else jnp.minimum(lengths, ring[0])
        steps = (fetch + pages * bs - 1) // (pages * bs)
        ends = jnp.cumsum(steps)
        i = jnp.arange(B * nj, dtype=jnp.int32)
        rows = jnp.minimum(jnp.sum(i[:, None] >= ends[None, :], axis=1),
                           B - 1)
        scalars += [rows.astype(jnp.int32),
                    (i - (ends - steps)[rows]).astype(jnp.int32)]
        grid, semantics = (ends[-1],), ("arbitrary",)
    else:
        grid, semantics = (B, nj), ("parallel", "arbitrary")
    params = (None if interpret else pltpu.CompilerParams(
        dimension_semantics=semantics))
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            grid=grid,
            in_specs=([pl.BlockSpec((1,) + width, row)]
                      + [page_spec(i) for i in range(pages)] * 2),
            out_specs=pl.BlockSpec((1,) + width, row),
            scratch_shapes=[
                pltpu.VMEM((pages * bs, D), operand_dtype),
                pltpu.VMEM((pages * bs, D), operand_dtype),
                pltpu.VMEM((n_heads, 1), jnp.float32),
                pltpu.VMEM((n_heads, 1), jnp.float32),
                pltpu.VMEM((n_heads, Dq if group == 1 else hd),
                           jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((B,) + width, q.dtype),
        compiler_params=params,
        interpret=interpret,
        name="paged_decode_attention",
    )(*scalars,
      q[:, None, :] if group == 1 else q.reshape((B,) + width),
      *([k4] * pages), *([v4] * pages))
    return out[:, 0, :] if group == 1 else out.reshape(B, Dq)


# ---------------------------------------------------------------------------
# Paged decode over LATENT pages (multi-head latent attention, absorbed form)
# — one row of H query heads per sequence against pages of ONE pool whose row
# is key and value at once: ``[c | k_r]``, the compressed latent and the
# shared rotary key side by side (576 values at kv_lora_rank 512 + 64 rotary
# dims, in a row allocated at D = 640: the kernel copies pages itself, and a
# copy out of HBM takes whole 128-lane tiles; the device pads a 576-wide
# array's rows to 640 in any case). Every head reads the same row, so the scores of all heads are one
# product ``(H, D) x (D, T)`` with no block-diagonal query, and the output
# ``P (H, T) x tile[:, :d_v]`` reads the FIRST ``d_v`` columns of the same
# tile: a live row is fetched once a layer. The grid is the batch's rows
# alone; the kernel walks ITS row's pages in a loop whose trip count is the
# row's own (a latent page is 18 KB at block 16, a fifth of a microsecond of
# the chip's bandwidth for 128 positions: a grid step a chunk, as
# ``paged_decode_attention`` takes them, would spend more on the steps past
# a short row's end in a batch with a long one than on the rows). Pages are
# copied where they lie in the pool (HBM) into one of two VMEM tiles, the
# next chunk's while this one is computed; a chunk's pages past the row's
# last are its last page again, masked. Operands are rounded as the
# backend's default precision rounds a matmul's (bfloat16 on the TPU, not at
# all in the interpreted kernel); the online softmax and every sum are
# float32.
# ---------------------------------------------------------------------------
MLA_CHUNK = 1024   # positions a step of the kernel's loop


def _mla_decode_kernel(layer_ref, table_ref, len_ref, q_ref, pool_ref, o_ref,
                       buf, sem, m_scr, l_scr, acc_scr, *, pages: int,
                       bs: int, n_pages: int, d_v: int, scale: float,
                       op_dtype):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b = pl.program_id(0)
    length = len_ref[b]
    layer = layer_ref[0]
    H = acc_scr.shape[0]
    T = pages * bs
    last_page = (length - 1) // bs
    n_chunks = (length + T - 1) // T

    def copies(c, slot):
        """The copies of chunk ``c``'s pages into tile ``slot``: a page past
        the row's last is its last page again, so the whole tile is always
        written (a tile row nothing wrote could hold anything, and a masked
        score times a not-a-number is one), by a loop the compiler unrolls
        (copies started from a loop of the row's own page count ran a third
        slower: measured, PERF.md section 6, PR 41)."""
        out = []
        for i in range(pages):
            page = table_ref[b * n_pages
                             + jnp.minimum(c * pages + i, last_page)]
            out.append(pltpu.make_async_copy(
                pool_ref.at[layer, pl.ds(page * bs, bs)],
                buf.at[slot, pl.ds(i * bs, bs)], sem.at[slot]))
        return out

    m_scr[:] = jnp.full_like(m_scr, NEG_INF)
    l_scr[:] = jnp.zeros_like(l_scr)
    acc_scr[:] = jnp.zeros_like(acc_scr)
    q = q_ref[0].astype(op_dtype)
    for dma in copies(0, 0):
        dma.start()

    def chunk(c, carry):
        slot = c % 2

        @pl.when(c + 1 < n_chunks)
        def _ahead():
            for dma in copies(c + 1, 1 - slot):
                dma.start()

        for dma in copies(c, slot):
            dma.wait()
        rows = buf[slot].astype(op_dtype)
        s = _dot_f32(q, rows, trans_b=True) * scale
        pos = c * T + jax.lax.broadcasted_iota(jnp.int32, (H, T), 1)
        # the first chunk always holds position 0, so m is finite from
        # there on and a masked score's exp underflows to the correct 0
        s = jnp.where(pos < length, s, NEG_INF)
        m_prev = m_scr[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[:] = l_scr[:] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[:] = acc_scr[:] * alpha + _dot_f32(p.astype(op_dtype),
                                                   rows[:, :d_v])
        m_scr[:] = m_new
        return carry

    jax.lax.fori_loop(0, n_chunks, chunk, 0)
    o_ref[0] = (acc_scr[:] / l_scr[:]).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("block_size", "d_v", "scale", "chunk",
                                    "interpret", "operand_dtype"))
def mla_paged_decode(q, pool, layer, block_tables, lengths, block_size: int,
                     d_v: int, scale: float, chunk: int = MLA_CHUNK,
                     interpret: bool = None, operand_dtype=None):
    """Absorbed-form latent attention of ONE row of query heads per
    sequence over its own paged prefix.

    q: (B, H, D) float32, each head ``[q_nope Wuk' | q_rope | 0]``; pool:
    the WHOLE pool, (layers, slots, D), a row ``[c | k_r | 0]`` (D a
    multiple of 128: the rows' allocated width) and a page ``block_size``
    consecutive slots; layer: int32 scalar (an operand, so
    every layer of a program runs the same kernel); block_tables: (B, pages)
    int32 physical block ids, as wide as the caller likes (the width costs
    SMEM, not time); lengths: (B,) int32, each >= 1, the positions ``0 ..
    length - 1`` a row attends to (pages past it are neither fetched nor
    computed). Scores are ``q pool_row' * scale``; the values are the first
    ``d_v`` columns of the same rows. ``chunk``: positions a step of the
    kernel's loop. Returns (B, H, d_v) float32: each head's ``P c``, to be
    taken through its ``Wuv``."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if interpret is None:
        interpret = not _on_tpu()
    if operand_dtype is None:
        operand_dtype = jnp.float32 if interpret else jnp.bfloat16
    B, H, D = q.shape
    bs = block_size
    n_pages = block_tables.shape[1]
    pages = max(1, chunk // bs)

    def row(b, *_):
        return (b, 0, 0)

    kernel = functools.partial(_mla_decode_kernel, pages=pages, bs=bs,
                               n_pages=n_pages, d_v=d_v, scale=scale,
                               op_dtype=operand_dtype)
    params = (None if interpret else pltpu.CompilerParams(
        dimension_semantics=("parallel",)))
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B,),
            in_specs=[pl.BlockSpec((1, H, D), row),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((1, H, d_v), row),
            scratch_shapes=[
                pltpu.VMEM((2, pages * bs, D), pool.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.VMEM((H, 1), jnp.float32),
                pltpu.VMEM((H, 1), jnp.float32),
                pltpu.VMEM((H, d_v), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((B, H, d_v), jnp.float32),
        compiler_params=params,
        interpret=interpret,
        name="mla_paged_decode",
    )(jnp.asarray(layer, jnp.int32).reshape(1),
      block_tables.astype(jnp.int32).reshape(-1),
      lengths.astype(jnp.int32), q, pool)


# ---------------------------------------------------------------------------
# A LATER prefill chunk of multi-head latent attention over the latent rows
# its context's pages keep, in the expanded form: each head's keys ``[c
# Wuk_h | k_r]`` and values ``c Wuv_h`` are built a tile of context rows at a
# time in VMEM and attended to there, so they never reach HBM, are built
# once a head (not once a query tile) and only for the rows the chunk
# attends to. The grid is (head, context tile): a head's query rows, its
# output and its online-softmax state stay in VMEM across its tiles; a tile
# past the one that holds the chunk's last row keeps that tile's block index
# (the pipeline fetches nothing) and `pl.when` skips it. A tile's keys are
# ONE product of the stored rows with a (row width, head width) matrix that
# holds ``Wuk_h`` and, in the rotary rows, an identity (exact: a sum with one
# non-zero term is the term), so no row is cut at an unaligned lane. Query
# rows go in blocks, a block wholly before a tile's first row skipped, the
# diagonal masked by absolute positions (the chunk's first row is a run-time
# offset, any row). Keys and values of rows past the chunk's end are zeroed
# (another sequence's rows or the scratch block: a masked score times a
# not-a-number is one). Operands in the caller's dtype (bfloat16 on the TPU:
# keys, values and probabilities rounded to it where the XLA form rounds
# them), every sum and the online softmax float32.
# ---------------------------------------------------------------------------
# swept on a v5e (PR 42, `kernel_bench mla_chunk`: a chunk of 2048 rows at
# 2048 .. 22528, tiles of 512 / 1024 x 512 / 1024 and 2048 query rows):
# 1024 x 1024 fastest at every shape, 512 x 512 3-11% slower
MLA_CONTEXT_TILE = 1024   # context rows a grid step
MLA_QUERY_BLOCK = 1024    # query rows a block of a step


def _mla_chunk_kernel(pos_ref, q_ref, ctx_ref, wk_ref, wv_ref, o_ref, k_scr,
                      v_scr, m_scr, l_scr, acc_scr, *, bq: int, bk: int,
                      r: int, scale: float):
    import jax.experimental.pallas as pl

    t = pl.program_id(1)
    start, end = pos_ref[0], pos_ref[1]
    last = (end - 1) // bk
    k0 = t * bk

    @pl.when(t == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    def build(clip: bool):
        k = _dot_f32(ctx_ref[:], wk_ref[0])
        v = _dot_f32(ctx_ref[:, :r], wv_ref[0])
        if clip:
            k = jnp.where(k0 + jax.lax.broadcasted_iota(
                jnp.int32, k.shape, 0) < end, k, 0.0)
            v = jnp.where(k0 + jax.lax.broadcasted_iota(
                jnp.int32, v.shape, 0) < end, v, 0.0)
        k_scr[:] = k.astype(k_scr.dtype)
        v_scr[:] = v.astype(v_scr.dtype)

    pl.when(t < last)(lambda: build(False))
    pl.when(t == last)(lambda: build(True))

    for j in range(q_ref.shape[0] // bq):
        rows = slice(j * bq, (j + 1) * bq)
        q0 = start + j * bq        # the block's first row's position

        def accumulate(masked: bool, rows=rows, q0=q0):
            s = _dot_f32(q_ref[rows, :], k_scr[:], trans_b=True) * scale
            if masked:
                q_pos = q0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
                k_pos = k0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
                s = jnp.where(q_pos >= k_pos, s, NEG_INF)
            # every row's first tile holds key 0, so m is finite from there
            # on and a masked score's exp underflows to the correct 0
            m_prev = m_scr[rows, :]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_scr[rows, :] = (l_scr[rows, :] * alpha
                              + jnp.sum(p, axis=-1, keepdims=True))
            acc_scr[rows, :] = (acc_scr[rows, :] * alpha
                                + _dot_f32(p.astype(v_scr.dtype), v_scr[:]))
            m_scr[rows, :] = m_new

        _causal_three_way(
            jnp.logical_and(t <= last, q0 + bq - 1 >= k0),
            jnp.logical_and(t <= last, q0 >= k0 + bk - 1), accumulate)

    @pl.when(t == last)
    def _finish():
        o_ref[:] = (acc_scr[:] / l_scr[:]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_q", "block_k",
                                             "interpret"))
def mla_chunk_attention(q, ctx, wuk, wuv, start, length,
                        block_q: int = MLA_QUERY_BLOCK,
                        block_k: int = MLA_CONTEXT_TILE,
                        interpret: bool = None):
    """Causal attention of a chunk's query rows over the latent rows of its
    context, all heads in one call, in the expanded form.

    q: (C, H * hd), row ``i`` the query heads of position ``start + i``, head
    ``h`` in columns ``h * hd ...``, each ``[q_nope | q_rope]``; its dtype is
    the operands' (and the output's). ctx: (L, D) the latent rows of
    positions ``0 .. L - 1`` as the pages keep them, ``[c | k_r | 0]`` with
    ``c`` ``r`` wide and ``k_r`` ``hd - nope``; wuk: (H, r, nope), wuv: (H,
    r, v); start, length: int32 scalars. Row ``i`` attends to positions ``0
    .. start + i``; rows of ``ctx`` from ``start + length`` on weigh nothing,
    and whole tiles of them are never read. Scores are scaled by ``1 /
    sqrt(hd)``. Returns (C, H * v), the rows as ``Wo`` contracts them; rows
    from ``length`` on are pads."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if interpret is None:
        interpret = not _on_tpu()
    c = q.shape[0]
    n_ctx, d = ctx.shape
    h, r, nope = wuk.shape
    d_v, hd, dt = wuv.shape[2], q.shape[1] // h, q.dtype
    bq, bk = min(block_q, c), min(block_k, n_ctx)
    if c % bq or n_ctx % bk:
        raise ValueError(f"rows ({c}, {n_ctx}) must divide blocks "
                         f"({bq}, {bk})")
    # a head's keys from the stored row in one product: Wuk_h over c, the
    # identity over k_r, zeros over the pad
    wk = jnp.zeros((h, d, hd), dt).at[:, :r, :nope].set(wuk.astype(dt))
    wk = wk.at[:, r:r + hd - nope, nope:].set(jnp.eye(hd - nope, dtype=dt))
    pos = jnp.stack([jnp.asarray(start, jnp.int32),
                     jnp.asarray(start + length, jnp.int32)])

    def tile(_h, t, pos):        # never past the chunk's last row's tile
        return (jnp.minimum(t, (pos[1] - 1) // bk), 0)

    params = (None if interpret else pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"),
        vmem_limit_bytes=_ROWS_VMEM_LIMIT))
    return pl.pallas_call(
        functools.partial(_mla_chunk_kernel, bq=bq, bk=bk, r=r,
                          scale=1.0 / math.sqrt(hd)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(h, n_ctx // bk),
            in_specs=[pl.BlockSpec((c, hd), lambda i, _t, _p: (0, i)),
                      pl.BlockSpec((bk, d), tile),
                      pl.BlockSpec((1, d, hd), lambda i, _t, _p: (i, 0, 0)),
                      pl.BlockSpec((1, r, d_v),
                                   lambda i, _t, _p: (i, 0, 0))],
            out_specs=pl.BlockSpec((c, d_v), lambda i, _t, _p: (0, i)),
            scratch_shapes=[
                pltpu.VMEM((bk, hd), dt),
                pltpu.VMEM((bk, d_v), dt),
                pltpu.VMEM((c, 1), jnp.float32),
                pltpu.VMEM((c, 1), jnp.float32),
                pltpu.VMEM((c, d_v), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((c, h * d_v), dt),
        compiler_params=params,
        interpret=interpret,
        name="mla_chunk_attention",
    )(pos, q, ctx.astype(dt), wk, wuv.astype(dt))


# ---------------------------------------------------------------------------
# Grouped matmul over the experts a chip holds (sparse-expert layers).
#
# Rows arrive SORTED by expert and padded so that every tile of ``block_rows``
# rows belongs to one expert; ``tile_expert`` (scalar-prefetched) names it and
# picks the expert's weight block in the index map, so a tile streams exactly
# one expert's (K, block_cols) column blocks, K whole (no accumulator). Tiles
# from ``tiles_used`` on hold no row: their index maps repeat the last used
# tile's blocks, so nothing is fetched for them, their body is skipped and
# their output rows are zero. An expert no row was routed to has no tile: its
# weights are never read. Operands are rounded as the backend's default
# precision rounds a matmul's (bfloat16 on the TPU, not at all in the
# interpreted kernel on the CPU); sums are float32.
# ---------------------------------------------------------------------------
def _moe_gmm_kernel(te_ref, used_ref, x_ref, w_ref, o_ref, *, op_dtype):
    import jax.experimental.pallas as pl

    del te_ref   # read by the index maps
    i = pl.program_id(0)

    @pl.when(i < used_ref[0])
    def _tile():
        o_ref[:] = jax.lax.dot_general(
            x_ref[:].astype(op_dtype), w_ref[:].astype(op_dtype),
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(i >= used_ref[0])
    def _empty():
        o_ref[:] = jnp.zeros_like(o_ref)


@functools.partial(jax.jit,
                   static_argnames=("block_rows", "block_cols", "interpret",
                                    "operand_dtype"))
def moe_grouped_matmul(x, w, tile_expert, tiles_used, block_rows: int,
                       block_cols: int = 256, interpret: bool = None,
                       operand_dtype=None):
    """``out[tile t] = x[tile t] @ w[tile_expert[t]]`` for the first
    ``tiles_used`` tiles of ``block_rows`` rows, zeros after them.

    x: (P, K), P a multiple of ``block_rows``; w: (E, K, N), N a multiple
    of ``block_cols``; tile_expert: (P // block_rows,) int32 in [0, E);
    tiles_used: int32 scalar. Returns (P, N) float32."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if interpret is None:
        interpret = not _on_tpu()
    if operand_dtype is None:
        operand_dtype = jnp.float32 if interpret else jnp.bfloat16
    P, K = x.shape
    _, _, N = w.shape
    bn = min(block_cols, N)
    if P % block_rows or N % bn:
        raise ValueError(f"rows {P} / columns {N} must divide into tiles "
                         f"of {block_rows} x {bn}")
    nt, nj = P // block_rows, N // bn

    def last(i, used_ref):
        return jnp.maximum(jnp.minimum(i, used_ref[0] - 1), 0)

    def x_index(i, j, te_ref, used_ref):
        return (last(i, used_ref), 0)

    def w_index(i, j, te_ref, used_ref):
        return (te_ref[last(i, used_ref)], 0,
                jnp.where(i < used_ref[0], j, nj - 1))

    kernel = functools.partial(_moe_gmm_kernel, op_dtype=operand_dtype)
    params = (None if interpret else pltpu.CompilerParams(
        dimension_semantics=("arbitrary", "arbitrary")))
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(nt, nj),
            in_specs=[pl.BlockSpec((block_rows, K), x_index),
                      pl.BlockSpec((None, K, bn), w_index)],
            out_specs=pl.BlockSpec((block_rows, bn),
                                   lambda i, j, *_: (i, j))),
        out_shape=jax.ShapeDtypeStruct((P, N), jnp.float32),
        compiler_params=params,
        interpret=interpret,
        name="moe_grouped_matmul",
    )(tile_expert.astype(jnp.int32),
      jnp.asarray(tiles_used, jnp.int32).reshape(1), x, w)


# ---------------------------------------------------------------------------
# Selective scan (Mamba-1) -- the recurrence of a prefill launch's rows,
# ``s_t = exp(dt_t a) * s_{t-1} + (dt_t u_t) b_t'``, ``y_t = s_t c_t``, as
# one kernel a layer: the (d_state, channels) state stays in VMEM and a loop
# walks the rows, so HBM sees dt and u once on the way in and y once on the
# way out; the (rows, d_state, d_inner) decay and drive of the XLA form
# (``ssm_scan_reference``) never exist. Layout: the CHANNELS fill whole
# (8, 128) tiles -- dt, u and y are handed over as (rows, d_inner / 128, 128),
# so a row's channels are whole tiles and so is each of the ``d_state`` state
# rows -- and ``b_t[k]``, ``c_t[k]`` are scalars read from SMEM: a row is
# ``d_state`` multiply-adds over full tiles with no broadcast and no
# reduction across a tile. The grid is (channel blocks, row blocks): the
# channel axis is independent, the row axis carries the state in scratch from
# one row block to the next. Float32 throughout; a row with ``dt == 0``
# leaves the state as it was (decay 1, drive 0).
# ---------------------------------------------------------------------------
_SCAN_BLOCK_BYTES = 2 << 20    # one of dt, u, y a grid step (each held twice)


def ssm_scan_reference(dt, u, bm, cm, a, s0=None):
    """The same recurrence in plain XLA: a ``lax.scan`` over chunks of 128
    rows, inside a chunk an associative scan over (rows, d_state, d_inner)
    decay and drive tensors. What the kernel is compared with; no served
    program calls it. Shapes and results as :func:`ssm_scan`."""
    s_len, di = dt.shape
    n = a.shape[0]
    t = math.gcd(128, s_len)

    def combine(x, y):
        return x[0] * y[0], y[0] * x[1] + y[1]

    def body(s0, inp):
        dt_c, u_c, b_c, c_c = inp
        decay = jnp.exp(dt_c[:, None, :] * a[None])           # (t, n, di)
        drive = (dt_c * u_c)[:, None, :] * b_c[:, :, None]
        dec, drv = jax.lax.associative_scan(combine, (decay, drive), axis=0)
        s = dec * s0[None] + drv
        return s[-1], jnp.sum(s * c_c[:, :, None], axis=1)

    s_end, ys = jax.lax.scan(
        body, jnp.zeros((n, di), jnp.float32) if s0 is None else s0,
        (dt.reshape(-1, t, di), u.reshape(-1, t, di),
         bm.reshape(-1, t, n), cm.reshape(-1, t, n)))
    return s_end, ys.reshape(s_len, di)


def _ssm_scan_kernel(bc_ref, dt_ref, u_ref, a_ref, s0_ref, y_ref, send_ref,
                     s_scr, *, rows: int, n: int):
    import jax.experimental.pallas as pl

    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        s_scr[:] = s0_ref[:]

    def row(t, carry):
        dt = dt_ref[t]                       # (channel tiles, 128)
        du = dt * u_ref[t]
        y = jnp.zeros_like(dt)
        for k in range(n):                   # b_t[k], c_t[k]: SMEM scalars
            s = (jnp.exp(dt * a_ref[k]) * s_scr[k]
                 + bc_ref[t * 2 * n + k] * du)
            s_scr[k] = s
            y = y + bc_ref[t * 2 * n + n + k] * s
        y_ref[t] = y
        return carry

    jax.lax.fori_loop(0, rows, row, 0)

    @pl.when(i == pl.num_programs(0) - 1)
    def _finish():
        send_ref[:] = s_scr[:]


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssm_scan(dt, u, bm, cm, a, s0=None, interpret: bool = None):
    """``s_t = exp(dt_t a) * s_{t-1} + (dt_t u_t) b_t'`` over all rows from
    ``s0`` (zeros where it is left out), ``y_t = s_t c_t``. dt, u (S, di);
    bm, cm (S, n); a, s0 (n, di); float32. Returns the last state (n, di)
    and y (S, di). The grid walks blocks of rows, each holding the WHOLE
    channel axis (the fastest form on the chip at d_inner 5120, PERF.md
    section 6, PR 36), as 128 channels a lane row where ``di`` divides so
    and as one lane row otherwise; a block has as many rows as keep one
    operand's block under ``_SCAN_BLOCK_BYTES``, in units of the rows whose
    B and C fill whole SMEM tiles (32 at ``n`` 16), S padded to such a unit
    with ``dt == 0`` rows."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if interpret is None:
        interpret = not _on_tpu()
    S, di = dt.shape
    n = a.shape[0]
    lanes = 128 if di % 128 == 0 else di
    tiles = di // lanes
    unit = 1024 // math.gcd(1024, 2 * n)
    f32 = functools.partial(jnp.asarray, dtype=jnp.float32)
    dt, u, bm, cm = (jnp.pad(f32(x), ((0, -S % unit), (0, 0)))
                     for x in (dt, u, bm, cm))
    sp = dt.shape[0]
    rows = unit * _fit_block(
        sp // unit, max(1, _SCAN_BLOCK_BYTES // (di * 4 * unit)))
    if s0 is None:
        s0 = jnp.zeros((n, di), jnp.float32)
    by_row = pl.BlockSpec((rows, tiles, lanes), lambda i: (i, 0, 0))
    by_state = pl.BlockSpec((n, tiles, lanes), lambda i: (0, 0, 0))
    y, s_end = pl.pallas_call(
        functools.partial(_ssm_scan_kernel, rows=rows, n=n),
        grid=(sp // rows,),
        in_specs=[pl.BlockSpec((rows * 2 * n,), lambda i: (i,),
                               memory_space=pltpu.SMEM),
                  by_row, by_row, by_state, by_state],
        out_specs=[by_row, by_state],
        out_shape=[jax.ShapeDtypeStruct((sp, tiles, lanes), jnp.float32),
                   jax.ShapeDtypeStruct((n, tiles, lanes), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((n, tiles, lanes), jnp.float32)],
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="ssm_scan",
    )(jnp.concatenate([bm, cm], axis=1).reshape(-1),
      dt.reshape(sp, tiles, lanes), u.reshape(sp, tiles, lanes),
      f32(a).reshape(n, tiles, lanes), f32(s0).reshape(n, tiles, lanes))
    return s_end.reshape(n, di), y.reshape(sp, di)[:S]


# ---------------------------------------------------------------------------
# Fused softmax cross-entropy — the other canonical memory-bound fusion:
# per row, one VMEM pass computes max / logsumexp / target logit without
# materializing the [rows, V] log-softmax in HBM.
# ---------------------------------------------------------------------------
def softmax_xent_reference(logits, targets):
    """Mean negative log-likelihood; logits [N, V], targets [N] int."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    ll = jnp.take_along_axis(logp, targets[:, None], axis=-1)[:, 0]
    return -jnp.mean(ll)


_XENT_BLOCK_BYTES = 2 << 20   # one (rows, V) float32 logits block in VMEM


def _xent_kernel(logits_ref, targets_ref, o_ref):
    x = logits_ref[:].astype(jnp.float32)          # [bn, V]
    t = targets_ref[:]                             # [bn, 1]
    m = jnp.max(x, axis=-1, keepdims=True)
    lse = m + jnp.log(jnp.sum(jnp.exp(x - m), axis=-1, keepdims=True))
    cols = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    picked = jnp.sum(jnp.where(cols == t, x, 0.0), axis=-1, keepdims=True)
    o_ref[:] = lse - picked                        # per-row NLL


def _xent_forward_rows(logits, targets, block_rows: int, interpret: bool):
    """Per-row NLL via the fused kernel; rows padded to the block size and
    masked out of the caller's mean (tiny-divisor row counts must not
    degrade into a 1-row grid)."""
    import jax.experimental.pallas as pl

    n, v = logits.shape
    bn = min(block_rows, max(n, 1))
    if not interpret:
        # a (bn, V) block is double-buffered and the kernel body holds a
        # few block-sized temporaries; Mosaic's scoped VMEM limit is
        # 16 MiB, which 256 rows of a 32k vocabulary exceed fourfold
        bn = min(bn, max(8, _XENT_BLOCK_BYTES // (4 * v) // 8 * 8))
    n2 = ((n + bn - 1) // bn) * bn
    if n2 != n:
        logits = jnp.pad(logits, ((0, n2 - n), (0, 0)))
        targets = jnp.pad(targets, (0, n2 - n))
    nll = pl.pallas_call(
        _xent_kernel,
        grid=(n2 // bn,),
        in_specs=[
            pl.BlockSpec((bn, v), lambda i: (i, 0)),
            pl.BlockSpec((bn, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((bn, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n2, 1), jnp.float32),
        interpret=interpret,
        name="softmax_xent",
    )(logits, targets.astype(jnp.int32)[:, None])
    return nll[:n, 0]


@jax.custom_vjp
def _softmax_xent_custom(logits, targets):
    return jnp.mean(_xent_forward_rows(logits, targets, 256, not _on_tpu()))


def _softmax_xent_fwd(logits, targets):
    return _softmax_xent_custom(logits, targets), (logits, targets)


def _softmax_xent_bwd(res, g):
    # d(mean NLL)/dlogits = (softmax - onehot) / N; the backward stays a
    # plain XLA softmax (already fused well) — the kernel wins the forward
    logits, targets = res
    n = logits.shape[0]
    p = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    onehot = jax.nn.one_hot(targets, logits.shape[1], dtype=jnp.float32)
    return ((g * (p - onehot) / n).astype(logits.dtype), None)


_softmax_xent_custom.defvjp(_softmax_xent_fwd, _softmax_xent_bwd)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def softmax_xent(logits, targets, block_rows: int = 256,
                 interpret: bool = None):
    """Fused mean cross-entropy; logits [N, V], targets [N] int.
    Differentiable (custom VJP) so it drops into training losses."""
    n = logits.shape[0]
    if n == 0:
        return jnp.float32(0.0)
    if block_rows == 256 and interpret is None:
        return _softmax_xent_custom(logits, targets)
    if interpret is None:
        interpret = not _on_tpu()
    return jnp.mean(_xent_forward_rows(logits, targets, block_rows,
                                       interpret))
