"""Toy sharded transformer for the serving plane.

Small enough to decode on the CPU test substrate, shaped enough that every
device-side mechanism in the repo carries weight on the request path:

- **Weights by handle** — parameters are packed into one flat float32
  buffer, crossed host→device once and registered in the ``DeviceStore``
  (``adopt``); compute looks them up by handle and unpacks device-side,
  so the serving plane owns no host-resident copy.
- **Paged KV** — prefill scatters K/V into the :class:`PagedKVCache`
  pools at block-table slots; decode appends the new token's K/V and
  attends over the context's pages, all inside ONE jitted program per
  engine step (donated pools → in-place updates, one dispatch for the
  whole mixed batch — the op-coalescing trick the device lane's dispatch
  thread plays, applied to the decode path). On a TPU the attention is
  the paged kernel of ``tpu/pallas_ops.py``: it reads the pages where
  they lie, through the block table, as far as each row's length; on the
  CPU substrate (and as the kernel's oracle) it gathers the context
  padded to the program's bucket.
- **Flash-attention prefill** — prompt self-attention runs the Pallas
  flash kernel from ``tpu/pallas_ops.py`` (interpret-mode on CPU), with
  the O(S²) reference as the numerics oracle; long prompts route through
  the ring-attention path (``tpu/ring.py``) which shard_maps across the
  ``sp`` mesh axis.

Shapes are bucketed (batch to powers of two, sequence to block-size
multiples) so the jit cache stays bounded across traffic mixes.
"""

from __future__ import annotations

import functools
import threading
from typing import List, Optional, Sequence, Tuple

import numpy as np

from brpc_tpu.profiling.registry import span as _span
from brpc_tpu.profiling.registry import wait_span as _wait_span
from brpc_tpu.serving.hybrid_model import (DECODE_COUNTERS, _next_pow2,
                                           decode_family, decode_lines)
from brpc_tpu.serving.kv_cache import PagedKVCache

# the counter families every ``ServingEngine.snapshot()`` carries, None where
# its model keeps none: ``decode`` (the toy block's, here, and
# Command A+'s, serving/moe_model.py) and the hybrid lane's ``moe``,
# ``scan`` and ``mla`` (serving/hybrid_model.py, serving/glm_model.py). A
# model reports these and any others by name through ``counters()`` and
# formats their ``/serving`` lines through ``counter_lines(snapshot)``; the
# engine names none of them.
COUNTER_FAMILIES = ("decode", "moe", "scan", "mla")


class ModelConfig:
    def __init__(self, vocab: int = 512, d_model: int = 64,
                 n_heads: int = 4, n_layers: int = 2,
                 max_context: int = 1024, seed: int = 0,
                 attn: str = "auto", ring_threshold: int = 4096):
        if d_model % n_heads:
            raise ValueError("d_model must divide n_heads")
        self.vocab = vocab
        self.d_model = d_model
        self.n_heads = n_heads
        self.n_layers = n_layers
        self.max_context = max_context
        self.seed = seed
        # "auto": flash kernel on TPU, reference einsum on the CPU
        # substrate (interpret-mode Pallas is correct but slow); tests pin
        # "flash" to exercise the kernel path end to end.
        self.attn = attn
        self.ring_threshold = ring_threshold

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    @property
    def kv_dim(self) -> int:
        return self.d_model


def _prefill_bucket(s: int) -> int:
    """The prefill program's padded length for a prompt of ``s``."""
    bucket = max(16, _next_pow2(s))
    if bucket > 128:
        bucket = ((s + 127) // 128) * 128  # flash wants S % 128 == 0
    return bucket


def _decode_buckets(n_rows: int, tables, block_size: int):
    """The decode program's padded (rows, context) for a batch."""
    max_blocks = max(len(t) for t in tables)
    return (max(2, _next_pow2(n_rows)),
            max(2, _next_pow2(max_blocks)) * block_size)


def _rms(x):
    import jax
    import jax.numpy as jnp

    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + 1e-6)


def _prefill_attention(qkv, n_heads: int, use_flash: bool):
    """Causal self-attention over one prompt's packed projection, (S, 3 x
    heads x hd) as ``h @ wqkv`` made it, to (S, heads x hd) as ``wo``
    contracts it: the one spelling the single-device and mesh prefill
    programs share. The kernel is ONE call of the folded flash forward for
    all heads: it reads a head where the projection put it (a column block,
    lane-aligned at hd = 128) and is handed the packed rows three times, so
    nothing is split or transposed around it."""
    import jax
    import jax.numpy as jnp

    from brpc_tpu.tpu import pallas_ops

    s, width = qkv.shape
    hd = width // (3 * n_heads)
    if use_flash:
        return pallas_ops.flash_attention_rows(
            qkv, qkv, qkv, n_heads, hd, heads_at=(0, n_heads, 2 * n_heads))
    q, k, v = (x.reshape(s, n_heads, hd).transpose(1, 0, 2)
               for x in jnp.split(qkv, 3, axis=-1))
    out = jax.vmap(functools.partial(pallas_ops.attention_reference,
                                     causal=True))(q, k, v)
    return out.transpose(1, 0, 2).reshape(s, n_heads * hd)


def _block_tables(tables, rows: int, n_pages: int) -> np.ndarray:
    """Host-side: each row's physical block ids, padded with scratch
    block 0 to the program's table width."""
    out = np.zeros((rows, n_pages), dtype=np.int32)
    for i, table in enumerate(tables):
        out[i, :len(table)] = table
    return out


def _gather_attention(q, kpool_l, vpool_l, slot_tables, mask, n_heads: int):
    """One query row (B, D) over its padded context, copied out of one
    layer's pool: (B, L, D) of K and of V whatever the rows' lengths."""
    import jax
    import jax.numpy as jnp

    B, L = slot_tables.shape
    hd = q.shape[-1] // n_heads
    with jax.named_scope("kv_gather"):
        ks = kpool_l[slot_tables]                     # (B, L, D)
        vs = vpool_l[slot_tables]
    qh = q.reshape(B, n_heads, hd)
    kh = ks.reshape(B, L, n_heads, hd)
    vh = vs.reshape(B, L, n_heads, hd)
    s = jnp.einsum("bhd,blhd->bhl", qh, kh) / np.sqrt(hd)
    s = jnp.where(mask[:, None, :], s, -1e30)
    patt = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhl,blhd->bhd", patt, vh).reshape(B, -1)


def _decode_body(cfg: ModelConfig, params, kpool, vpool, tokens, positions,
                 block_tables, B: int, L: int, paged: bool):
    """The fused decode math for ONE device's pool slice — shared verbatim
    by the single-device jit and the mesh shard_map body
    (serving/mesh_model.py), so sharded greedy decode is token-identical
    to single-device by construction. Returns the pools and each row's
    greedy next token."""
    import jax
    import jax.numpy as jnp

    kpool, vpool, logits = _decode_logits(cfg, params, kpool, vpool, tokens,
                                          positions, block_tables, B, L,
                                          paged)
    with jax.named_scope("head"):
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return kpool, vpool, nxt


def _decode_logits(cfg: ModelConfig, params, kpool, vpool, tokens,
                   positions, block_tables, B: int, L: int, paged: bool):
    """``_decode_body`` up to the logits (B, V), which the hardware lane
    compares between the two attention paths.

    tokens (B,), positions (B,), block_tables (B, L / block_size): the
    physical block of every page of a row's context (pads -> scratch
    block 0). Row b attends to positions 0 .. positions[b] of its table,
    its own included: every row's K/V is written before any row's
    attention of the same layer. ``paged``: attention reads the pages in
    place through the kernel; otherwise it gathers the padded context
    (the CPU path, and the oracle the kernel is tested against)."""
    import jax
    import jax.numpy as jnp

    from brpc_tpu.tpu import pallas_ops

    H = cfg.n_heads
    bs = L // block_tables.shape[1]
    scope = jax.named_scope   # metadata only: names a device op's part
    x = params["embed"][tokens]                       # (B, D)
    page = jnp.take_along_axis(block_tables, positions[:, None] // bs,
                               axis=1)[:, 0]
    write = page * bs + positions % bs                # (B,)
    if not paged:
        slot_tables = (block_tables[:, :, None] * bs
                       + jnp.arange(bs)).reshape(B, L)
        mask = (jnp.arange(L)[None, :]
                <= positions[:, None])                # (B, L)
    for l in range(cfg.n_layers):
        with scope("kv_write"):
            h = _rms(x)
            qkv = h @ params[f"wqkv{l}"]
            q, k, vv = jnp.split(qkv, 3, axis=-1)
            kpool = kpool.at[l, write].set(k)
            vpool = vpool.at[l, write].set(vv)
        with scope("attention"):
            if paged:
                attn = pallas_ops.paged_decode_attention(
                    q, kpool, vpool, l, block_tables, positions + 1,
                    n_heads=H, block_size=bs)
            else:
                attn = _gather_attention(q, kpool[l], vpool[l],
                                         slot_tables, mask, H)
            x = x + attn @ params[f"wo{l}"]
        with scope("mlp"):
            h2 = _rms(x)
            x = x + jax.nn.relu(h2 @ params[f"w1{l}"]) @ params[f"w2{l}"]
    with scope("head"):
        logits = _rms(x) @ params["embed"].T          # (B, V)
    return kpool, vpool, logits


class TinyTransformer:
    """Weights + the fused prefill/decode programs over a PagedKVCache."""

    # the step-dispatch contract the engine asserts under BRPC_TPU_CHECK:
    # decode_step is ONE fused launch + ONE host materialization, counted
    # through tpu/device_lane.step_dispatch
    FUSED_STEP = True

    def __init__(self, config: ModelConfig, kv: PagedKVCache,
                 store=None, mesh=None):
        import jax

        from brpc_tpu.tpu.device_lane import global_store

        self.config = config
        self.kv = kv
        self.store = store if store is not None else kv.store
        self.mesh = mesh
        self._lock = threading.Lock()
        self._prefill_cache = {}
        self._decode_cache = {}   # (b_bucket, l_bucket) -> program, path
        self._decode_fns = {}     # the jitted programs those keys share
        # what the decode launches of this instance ran and read: pages
        # the rows' lengths cover against pages of the padded bucket (what
        # the gather copies); ``counters()`` reports them
        self.decode_counters = dict.fromkeys(DECODE_COUNTERS, 0)

        # ---- weights: pack host-side once, stream into HBM by handle
        flat, self._offsets = self._init_weights(config)
        self.param_handle, self.param_nbytes = self.store.adopt(
            jax.device_put(flat, self.store.device))
        self._params = self._unpack_params(
            self.store.lookup(self.param_handle))
        if mesh is not None:
            # replicate params across the mesh; jit follows the placement
            from brpc_tpu.tpu.mesh import named_sharding

            self._params = jax.device_put(
                self._params, named_sharding(mesh))

    # ------------------------------------------------------------- weights
    def _init_weights(self, cfg: ModelConfig):
        rng = np.random.RandomState(cfg.seed)
        d, v = cfg.d_model, cfg.vocab
        shapes = [("embed", (v, d))]
        for l in range(cfg.n_layers):
            shapes += [(f"wqkv{l}", (d, 3 * d)), (f"wo{l}", (d, d)),
                       (f"w1{l}", (d, 2 * d)), (f"w2{l}", (2 * d, d))]
        offsets = []
        pos = 0
        parts = []
        for name, shape in shapes:
            n = int(np.prod(shape))
            offsets.append((name, pos, shape))
            parts.append((rng.standard_normal(n) *
                          (0.5 / np.sqrt(shape[0]))).astype(np.float32))
            pos += n
        return np.concatenate(parts), offsets

    def _unpack_params(self, flat):
        """Device-side: slice the staged float32 buffer into the weight
        pytree (no host copy). The buffer is staged as float32, not
        bytes: a ``u8.reshape(-1, 4)`` bitcast has a minor dimension of
        4, which a TPU layout pads to a full lane tile."""
        import jax

        @jax.jit
        def unpack(f32):
            return {name: f32[pos:pos + int(np.prod(shape))].reshape(shape)
                    for name, pos, shape in self._offsets}

        return unpack(flat)

    # ----------------------------------------------------------- attention
    def _use_flash(self) -> bool:
        if self.config.attn == "flash":
            return True
        if self.config.attn == "reference":
            return False
        from brpc_tpu.tpu.pallas_ops import _on_tpu

        return _on_tpu()

    def _decode_paged(self, b_bucket: int, n_pages: int) -> bool:
        """Which attention a decode program of this shape runs, from what
        the code can observe: the paged kernel on a TPU (``_use_flash``'s
        rule for prefill) while the block table fits the kernel's scalar
        memory, the gather of the padded context elsewhere."""
        from brpc_tpu.tpu import pallas_ops

        return (pallas_ops._on_tpu() and
                b_bucket * pallas_ops.paged_table_pages(n_pages) * 4
                <= pallas_ops.PAGED_TABLE_BYTES)

    # ------------------------------------------------------------- prefill
    def _prefill_fn(self, s_bucket: int, use_flash: bool):
        import jax
        import jax.numpy as jnp

        cfg = self.config
        H = cfg.n_heads

        def rms(x):
            return x * jax.lax.rsqrt(
                jnp.mean(jnp.square(x), axis=-1, keepdims=True) + 1e-6)

        scope = jax.named_scope   # as in _decode_body

        def impl(params, kpool, vpool, tokens, slots, length):
            x = params["embed"][tokens]                      # (S, D)
            for l in range(cfg.n_layers):
                with scope("kv_write"):
                    h = rms(x)
                    qkv = h @ params[f"wqkv{l}"]
                    _, k, vv = jnp.split(qkv, 3, axis=-1)
                    kpool = kpool.at[l, slots].set(k)
                    vpool = vpool.at[l, slots].set(vv)
                with scope("attention"):
                    attn = _prefill_attention(qkv, H, use_flash)
                    x = x + attn @ params[f"wo{l}"]
                with scope("mlp"):
                    h2 = rms(x)
                    x = x + (jax.nn.relu(h2 @ params[f"w1{l}"])
                             @ params[f"w2{l}"])
            with scope("head"):
                last = rms(x[length - 1])
                logits = last @ params["embed"].T
                nxt = jnp.argmax(logits).astype(jnp.int32)
            return kpool, vpool, nxt

        return jax.jit(impl, donate_argnums=(1, 2))

    def _slots_for(self, table: Sequence[int], upto: int,
                   pad_to: int) -> np.ndarray:
        """Flat pool slot per token position (host-side); padded positions
        point at scratch block 0."""
        bs = self.kv.block_size
        t = np.arange(pad_to, dtype=np.int32)
        tab = np.asarray(table, dtype=np.int32)
        blocks = np.where(t < upto, tab[np.minimum(t // bs,
                                                   len(tab) - 1)], 0)
        live = (t < upto).astype(np.int32)
        return (blocks * bs + (t % bs)) * live

    def prefill(self, tokens: np.ndarray, table: Sequence[int]) -> int:
        """Run prompt prefill for ONE sequence: scatter its K/V pages into
        the pool and return the first generated token (greedy). Long
        prompts take the ring-attention path."""
        s = len(tokens)
        bucket = _prefill_bucket(s)
        with _span("model.prefill", n=s, bucket=bucket):
            if s >= self.config.ring_threshold:
                return self._prefill_ring(tokens, table)
            self.kv.assert_writable(table, 0, s)
            with _span("model.prep"):
                use_flash = self._use_flash()
                key = (bucket, use_flash)
                with self._lock:
                    fn = self._prefill_cache.get(key)
                    if fn is None:
                        fn = self._prefill_fn(bucket, use_flash)
                        self._prefill_cache[key] = fn
                toks = np.zeros(bucket, dtype=np.int32)
                toks[:s] = tokens
                slots = self._slots_for(table, s, bucket)
            from brpc_tpu.tpu.device_lane import step_dispatch
            with _span("model.launch"):
                step_dispatch.note_launch(1)
                kpool, vpool, nxt = fn(self._params, self.kv.k_pool,
                                       self.kv.v_pool, toks, slots, s)
                self.kv.update_pools(kpool, vpool)
            with _wait_span("model.sync"):
                first = int(nxt)
                step_dispatch.note_host_sync()
            return first

    def prefill_suffix(self, tokens: np.ndarray, table: Sequence[int],
                       start: int) -> int:
        """Prefill only ``tokens[start:]`` against a table whose first
        ``start`` positions already hold committed K/V (a forked prefix
        chain). Runs through the SAME fused decode program as steady-state
        decode — one row per suffix token, each attending over the paged
        context up to itself — so a cache hit costs one decode-shaped launch and the
        written K/V (and the sampled token, row ``s - 1``'s argmax) are
        bit-identical to what cold prefill produces. Inherits to the mesh
        model unchanged: decode_step places rows by ``table.shard``."""
        s = len(tokens)
        if not 0 < start < s:
            raise ValueError(f"suffix start {start} outside (0, {s})")
        with _span("model.prefill", n=s, start=start):
            suffix = np.asarray(tokens[start:], dtype=np.int32)
            positions = np.arange(start, s, dtype=np.int32)
            out = self.decode_step(suffix, positions,
                                   [table] * (s - start))
            return int(out[-1])

    def _prefill_ring(self, tokens: np.ndarray,
                      table: Sequence[int]) -> int:
        """Long-context prefill: per-layer attention through the ring
        (sequence-sharded shard_map over the ``sp`` axis; single-device
        meshes degenerate to one hop). Layer loop runs host-side — prompts
        this long are rare and the per-layer ring call is itself fused."""
        import jax
        import jax.numpy as jnp

        from brpc_tpu.tpu import ring
        from brpc_tpu.tpu.mesh import default_mesh

        cfg = self.config
        H, hd = cfg.n_heads, cfg.head_dim
        mesh = self.mesh if (self.mesh is not None
                             and "sp" in self.mesh.axis_names) \
            else default_mesh("sp")
        n = mesh.shape["sp"]
        s = len(tokens)
        self.kv.assert_writable(table, 0, s)
        pad = ((s + n - 1) // n) * n
        p = self._params

        def rms(x):
            return x * jax.lax.rsqrt(
                jnp.mean(jnp.square(x), axis=-1, keepdims=True) + 1e-6)

        from brpc_tpu.tpu.device_lane import step_dispatch
        with _span("model.launch"):   # one launch a layer, none waited for
            toks = np.zeros(pad, dtype=np.int32)
            toks[:s] = tokens
            x = p["embed"][jnp.asarray(toks)]
            kpool, vpool = self.kv.k_pool, self.kv.v_pool
            slots = jnp.asarray(self._slots_for(table, s, pad))
            for l in range(cfg.n_layers):
                h = rms(x)
                qkv = h @ p[f"wqkv{l}"]
                q, k, vv = jnp.split(qkv, 3, axis=-1)
                kpool = kpool.at[l, slots].set(k)
                vpool = vpool.at[l, slots].set(vv)
                qh = q.reshape(1, pad, H, hd)
                kh = k.reshape(1, pad, H, hd)
                vh = vv.reshape(1, pad, H, hd)
                step_dispatch.note_launch(1)
                attn = ring.ring_attention(qh, kh, vh, mesh, "sp",
                                           causal=True)
                x = x + attn.reshape(pad, -1) @ p[f"wo{l}"]
                h2 = rms(x)
                x = x + jax.nn.relu(h2 @ p[f"w1{l}"]) @ p[f"w2{l}"]
            self.kv.update_pools(kpool, vpool)
            logits = rms(x[s - 1]) @ p["embed"].T
        with _wait_span("model.sync"):
            first = int(jnp.argmax(logits))
            step_dispatch.note_host_sync()
        return first

    # -------------------------------------------------------------- decode
    def _decode_fn(self, b_bucket: int, l_table: int, paged: bool):
        """The jitted decode program for ``b_bucket`` rows under block
        tables that span ``l_table`` positions."""
        import jax

        cfg = self.config

        def impl(params, kpool, vpool, tokens, positions, block_tables):
            return _decode_body(cfg, params, kpool, vpool, tokens,
                                positions, block_tables, b_bucket, l_table,
                                paged)

        return jax.jit(impl, donate_argnums=(1, 2))

    def _decode_program(self, b_bucket: int, l_bucket: int, positions,
                        groups: int = 1):
        """The jitted program of one (rows, context) bucket and the width
        of its block tables in pages, built on its first use, with this
        launch counted (``groups``: how many pool slices each run a
        bucket of rows). The gather body's table is the bucket's: it
        copies every column. The kernel reads a row's pages only as far
        as its length, so a column costs it a skipped grid step and no
        more: its tables are widened (``pallas_ops.paged_table_pages``)
        and the context buckets under one width
        share ONE compiled program a row bucket (a program is seconds of
        set-up in every process, from the compile cache or not)."""
        from brpc_tpu.tpu.pallas_ops import paged_table_pages

        bs = self.kv.block_size
        n_pages = l_bucket // bs
        key = (b_bucket, l_bucket)
        with self._lock:
            hit = self._decode_cache.get(key)
            if hit is None:
                paged = self._decode_paged(b_bucket, n_pages)
                width = paged_table_pages(n_pages) if paged else n_pages
                fn_key = (b_bucket, width, paged)
                fn = self._decode_fns.get(fn_key)
                if fn is None:
                    fn = self._decode_fn(b_bucket, width * bs, paged)
                    self._decode_fns[fn_key] = fn
                hit = (fn, paged, width)
                self._decode_cache[key] = hit
            fn, paged, width = hit
            c = self.decode_counters
            c["decode_launches_paged" if paged
              else "decode_launches_gather"] += 1
            c["decode_pages_live"] += int(
                ((np.asarray(positions, dtype=np.int64) + bs) // bs).sum())
            c["decode_pages_bucket"] += groups * b_bucket * n_pages
        return fn, width

    def decode_step(self, tokens: np.ndarray, positions: np.ndarray,
                    tables: List[Sequence[int]]) -> np.ndarray:
        """ONE fused device dispatch for the whole decode batch: append
        each sequence's token at its position, attend over its paged
        context, and return the next token per sequence
        (host-materialized once, here, not per token)."""
        B = len(tokens)
        b_bucket, l_bucket = _decode_buckets(B, tables, self.kv.block_size)
        with _span("model.decode", B=B, b_bucket=b_bucket,
                   l_bucket=l_bucket):
            self.kv.assert_writable_batch(tables, positions)
            with _span("model.prep"):
                fn, width = self._decode_program(b_bucket, l_bucket,
                                                 positions)
                toks = np.zeros(b_bucket, dtype=np.int32)
                toks[:B] = tokens
                pos = np.zeros(b_bucket, dtype=np.int32)
                pos[:B] = positions
                block_tables = _block_tables(tables, b_bucket, width)
            from brpc_tpu.tpu.device_lane import step_dispatch
            with _span("model.launch"):
                step_dispatch.note_launch(1)
                kpool, vpool, nxt = fn(self._params, self.kv.k_pool,
                                       self.kv.v_pool, toks, pos,
                                       block_tables)
                self.kv.update_pools(kpool, vpool)
            with _wait_span("model.sync"):
                out = np.asarray(nxt[:B])
                step_dispatch.note_host_sync()
            return out

    def verify_step(self, last_tokens: Sequence[int],
                    positions: Sequence[int], tables: List[Sequence[int]],
                    drafts: List[Sequence[int]]) -> List[np.ndarray]:
        """Speculative verify: ONE fused launch scoring every sequence's
        last committed token plus its k drafted tokens — k+1 rows per
        sequence flattened into the same fused decode program steady-state
        decode uses (the ``prefill_suffix`` trick, batched). Inside one
        launch every row's K/V write lands before any row's attention and
        a row's length limits row j to positions ≤ its own, so row j
        attends over rows 0..j-1's *same-launch* writes: the returned
        argmax per row is exactly what k+1 sequential decode steps would
        produce. One launch, one host materialization — the (1,1)
        dispatch invariant holds for arbitrary k. Returns one array of
        k_i+1 argmax tokens per sequence (``m_0..m_k``: the verifier's
        next-token at the last committed position and after each draft).
        Rows of a sequence share its table, so the mesh model's
        shard-grouped ``decode_step`` keeps them on the owning dp shard
        in order — verify inherits bit-identical tp/dp lowering with no
        mesh-specific code."""
        flat_tokens: List[int] = []
        flat_pos: List[int] = []
        flat_tables: List[Sequence[int]] = []
        counts: List[int] = []
        with _span("model.prep"):   # the rows; decode_step is the span
            for t0, p0, table, d in zip(last_tokens, positions, tables,
                                        drafts):
                row_toks = [int(t0)] + [int(x) for x in d]
                for j, tok in enumerate(row_toks):
                    flat_tokens.append(tok)
                    flat_pos.append(int(p0) + j)
                    flat_tables.append(table)
                counts.append(len(row_toks))
        out = self.decode_step(np.asarray(flat_tokens, dtype=np.int32),
                               np.asarray(flat_pos, dtype=np.int32),
                               flat_tables)
        res: List[np.ndarray] = []
        off = 0
        for c in counts:
            res.append(out[off:off + c])
            off += c
        return res

    # ------------------------------------------------------------ counters
    def counters(self):
        """The counter families this model keeps, by name, for
        ``ServingEngine.snapshot()`` (which copies them): ``decode``, the
        decode launches by attention path, the pages the rows' lengths
        covered against the pages of the padded buckets, and their ratio
        ``live_share``."""
        return {"decode": decode_family(self.decode_counters)}

    def counter_lines(self, snap) -> List[str]:
        """``/serving``'s line of the ``decode`` family in ``snap`` (an
        engine's snapshot): which path the launches took, and how much of
        the padded bucket (what the gather copies) the rows' lengths cover
        (what the paged kernel reads)."""
        return decode_lines(snap)

    # ------------------------------------------------------------- helpers
    def close(self) -> None:
        self.store.free(self.param_handle)

    def synth_prompt(self, length: int) -> np.ndarray:
        """Deterministic prompt for bench/replay traffic (keyed only by
        length so a dumped corpus replays bit-identically)."""
        v = self.config.vocab
        return ((np.arange(length, dtype=np.int64) * 31 + 7)
                % (v - 1)).astype(np.int32) + 1
