"""Kernels and device lanes on the real chip (VERDICT r2 #6: the
hardware-only coverage that the CPU-mesh suite permanently skips).

Every test here states a CORRECTNESS property; timing lives in
tools/kernel_bench.py.
"""

import numpy as np
import pytest

pytestmark = pytest.mark.hardware

jnp = pytest.importorskip("jax.numpy")
import jax  # noqa: E402


class TestKernelsOnChip:
    def test_flash_attention_mxu(self, tpu_device):
        from brpc_tpu.tpu.pallas_ops import (attention_reference,
                                             flash_attention)

        rng = np.random.default_rng(0)
        S, D = 1024, 128
        q = jnp.asarray(rng.normal(size=(S, D)), dtype=jnp.bfloat16)
        k = jnp.asarray(rng.normal(size=(S, D)), dtype=jnp.bfloat16)
        v = jnp.asarray(rng.normal(size=(S, D)), dtype=jnp.bfloat16)
        for causal in (False, True):
            out = flash_attention(q, k, v, causal=causal, interpret=False)
            ref = attention_reference(q, k, v, causal=causal)
            np.testing.assert_allclose(
                np.asarray(out, dtype=np.float32),
                np.asarray(ref, dtype=np.float32), rtol=0.1, atol=0.06)

    def test_flash_grouped_heads_on_chip(self, tpu_device):
        # the serving prefill's call at the rag-steady cell's shapes: 128
        # query heads over 8 K/V heads of 128, 2048 rows, bfloat16, causal,
        # ONE call; every query head against the reference on ITS K/V head
        from brpc_tpu.tpu.pallas_ops import (attention_reference,
                                             flash_attention_mha)

        rng = np.random.default_rng(3)
        H, G, S, D = 128, 8, 2048, 128
        q = jnp.asarray(rng.normal(size=(1, H, S, D)), dtype=jnp.bfloat16)
        k = jnp.asarray(rng.normal(size=(1, G, S, D)), dtype=jnp.bfloat16)
        v = jnp.asarray(rng.normal(size=(1, G, S, D)), dtype=jnp.bfloat16)
        out = flash_attention_mha(q, k, v, causal=True, interpret=False)
        assert out.shape == q.shape and out.dtype == jnp.bfloat16
        ref = jax.jit(jax.vmap(
            lambda q1, k1, v1: attention_reference(q1, k1, v1, causal=True)))(
                q[0], jnp.repeat(k[0], H // G, axis=0),
                jnp.repeat(v[0], H // G, axis=0))
        np.testing.assert_allclose(
            np.asarray(out[0], dtype=np.float32),
            np.asarray(ref, dtype=np.float32), rtol=0.1, atol=0.06)

    def test_flash_mha_bwd_on_chip(self, tpu_device):
        # the Pallas backward kernels under the NATIVE Mosaic lowering;
        # oracle = AD through the O(S^2) reference in f32
        from brpc_tpu.tpu.pallas_ops import (attention_reference,
                                             flash_attention_mha)

        rng = np.random.default_rng(7)
        B, H, S, D = 2, 2, 512, 128
        q = jnp.asarray(rng.normal(size=(B, H, S, D)), dtype=jnp.float32)
        k = jnp.asarray(rng.normal(size=(B, H, S, D)), dtype=jnp.float32)
        v = jnp.asarray(rng.normal(size=(B, H, S, D)), dtype=jnp.float32)

        def ref(q, k, v):
            f = lambda q1, k1, v1: attention_reference(q1, k1, v1,
                                                       causal=True)
            return jax.vmap(jax.vmap(f))(q, k, v)

        g = jax.grad(lambda q, k, v: jnp.sum(jnp.sin(flash_attention_mha(
            q, k, v, causal=True, interpret=False))), argnums=(0, 1, 2))(
                q, k, v)
        gr = jax.grad(lambda q, k, v: jnp.sum(jnp.sin(ref(q, k, v))),
                      argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g, gr):
            # bf16 MXU tiles inside the kernel vs f32 XLA reference
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=0.1, atol=0.05)

    def test_flash_carry_matches_one_shot(self, tpu_device):
        # carry form seeded with the identity state + one pass + normalize
        # == the one-shot kernel (the ring-hop contract)
        from brpc_tpu.tpu.pallas_ops import (NEG_INF, flash_attention,
                                             flash_attention_carry)

        rng = np.random.default_rng(1)
        S, D = 512, 128
        q = jnp.asarray(rng.normal(size=(S, D)), dtype=jnp.float32)
        k = jnp.asarray(rng.normal(size=(S, D)), dtype=jnp.float32)
        v = jnp.asarray(rng.normal(size=(S, D)), dtype=jnp.float32)
        m0 = jnp.full((S, 1), NEG_INF, dtype=jnp.float32)
        l0 = jnp.zeros((S, 1), dtype=jnp.float32)
        a0 = jnp.zeros((S, D), dtype=jnp.float32)
        m, l, acc = flash_attention_carry(q, k, v, m0, l0, a0, 0, 0,
                                          causal=True, interpret=False)
        out = acc / jnp.where(l == 0, 1.0, l)
        ref = flash_attention(q, k, v, causal=True, interpret=False)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-4, atol=1e-5)

    def test_carry_split_kv_matches_whole(self, tpu_device):
        # two sequential carry passes over split KV == one pass over all of
        # it (exactly what ring hops do)
        from brpc_tpu.tpu.pallas_ops import NEG_INF, flash_attention_carry

        rng = np.random.default_rng(2)
        S, D = 512, 128
        q = jnp.asarray(rng.normal(size=(S, D)), dtype=jnp.float32)
        k = jnp.asarray(rng.normal(size=(S, D)), dtype=jnp.float32)
        v = jnp.asarray(rng.normal(size=(S, D)), dtype=jnp.float32)
        m0 = jnp.full((S, 1), NEG_INF, dtype=jnp.float32)
        l0 = jnp.zeros((S, 1), dtype=jnp.float32)
        a0 = jnp.zeros((S, D), dtype=jnp.float32)
        m1, l1, a1 = flash_attention_carry(q, k[:256], v[:256], m0, l0, a0,
                                           0, 0, causal=True,
                                           interpret=False)
        m2, l2, a2 = flash_attention_carry(q, k[256:], v[256:], m1, l1, a1,
                                           0, 256, causal=True,
                                           interpret=False)
        out_split = a2 / jnp.where(l2 == 0, 1.0, l2)
        mw, lw, aw = flash_attention_carry(q, k, v, m0, l0, a0, 0, 0,
                                           causal=True, interpret=False)
        out_whole = aw / jnp.where(lw == 0, 1.0, lw)
        np.testing.assert_allclose(np.asarray(out_split),
                                   np.asarray(out_whole),
                                   rtol=1e-4, atol=1e-5)

    def test_fused_xent_on_chip(self, tpu_device):
        from brpc_tpu.tpu.pallas_ops import softmax_xent, softmax_xent_reference

        rng = np.random.default_rng(3)
        logits = jnp.asarray(rng.normal(size=(512, 2048)), dtype=jnp.float32)
        targets = jnp.asarray(rng.integers(0, 2048, size=(512,)))
        got = softmax_xent(logits, targets, interpret=False)
        want = softmax_xent_reference(logits, targets)
        np.testing.assert_allclose(float(got), float(want), rtol=1e-4)

    def test_rmsnorm_on_chip(self, tpu_device):
        from brpc_tpu.tpu.pallas_ops import rmsnorm, rmsnorm_reference

        rng = np.random.default_rng(4)
        x = jnp.asarray(rng.normal(size=(1024, 512)), dtype=jnp.bfloat16)
        w = jnp.asarray(rng.normal(size=(512,)), dtype=jnp.bfloat16)
        got = rmsnorm(x, w, interpret=False)
        want = rmsnorm_reference(x, w)
        np.testing.assert_allclose(np.asarray(got, dtype=np.float32),
                                   np.asarray(want, dtype=np.float32),
                                   rtol=0.05, atol=0.05)

    @pytest.mark.parametrize("rows,from_state", [(2048, True), (128, True),
                                                 (512, False), (3072, False)])
    def test_ssm_scan_on_chip(self, tpu_device, rows, from_state):
        # the hybrid cells' shapes: a chunk of `longdoc-steady` and its
        # smallest last chunk from a slot's state, `reason-steady`'s
        # window-sized and longest prefill buckets from zero; the last 100
        # rows pads (dt == 0), as the callers mask them
        from brpc_tpu.tpu.pallas_ops import ssm_scan, ssm_scan_reference

        rng = np.random.default_rng(rows)
        di, n = 5120, 16
        f = np.float32
        dt = np.log1p(np.exp(rng.normal(size=(rows, di)) - 4.0)).astype(f)
        dt[rows - 100:] = 0.0
        u = rng.normal(size=(rows, di)).astype(f)
        bm = rng.normal(size=(rows, n)).astype(f)
        cm = rng.normal(size=(rows, n)).astype(f)
        a = -np.broadcast_to(np.arange(1.0, n + 1)[:, None], (n, di)).astype(f)
        s0 = (0.1 * rng.normal(size=(n, di))).astype(f) if from_state \
            else None
        s_end, y = ssm_scan(dt, u, bm, cm, a, s0, interpret=False)
        want_s, want_y = ssm_scan_reference(
            *(jnp.asarray(x) for x in (dt, u, bm, cm, a)),
            None if s0 is None else jnp.asarray(s0))
        scale = float(jnp.max(jnp.abs(want_y)))
        assert float(jnp.max(jnp.abs(y - want_y))) <= 1e-4 * scale
        assert float(jnp.max(jnp.abs(s_end - want_s))) <= 1e-4 * max(
            1.0, float(jnp.max(jnp.abs(want_s))))
        live, _ = ssm_scan(dt[:rows - 100], u[:rows - 100],
                           bm[:rows - 100], cm[:rows - 100], a, s0,
                           interpret=False)
        np.testing.assert_array_equal(np.asarray(s_end), np.asarray(live))


class TestServingAndRingOnChip:
    """The two paths no hardware test had, and the chip refused (PR 21)."""

    @pytest.mark.parametrize("H,hd,S", [
        *((16, 128, S) for S in [16, 128, *range(384, 1537, 128)]),
        (4, 16, 64), (4, 16, 256)])
    def test_serving_prefill_attention_layout(self, tpu_device, H, hd, S):
        # the serving cell's shape and dtype: one prompt's packed
        # projection (S, 3 x 16 x 128) float32, through the one spelling
        # both prefill programs share, at 16 and 128 rows and at each of
        # prefill-closed's ten buckets; every head against the reference at
        # the configuration's stated precision (the TPU's default: both
        # operands of a product rounded to bfloat16, sums float32), which
        # is what the reference einsum runs at here. Heads of 16 are no
        # lane-aligned column block: they go heads first, through copies
        from brpc_tpu.serving.model import _prefill_attention

        rng = np.random.default_rng(S)
        qkv = jnp.asarray(rng.normal(size=(S, 3 * H * hd)) * 0.5,
                          dtype=jnp.float32)
        flash = jax.jit(lambda x: _prefill_attention(x, H, True))
        ref = jax.jit(lambda x: _prefill_attention(x, H, False))
        out = flash(qkv)
        assert out.shape == (S, H * hd) and out.dtype == jnp.float32
        want = np.asarray(ref(qkv))
        for h in range(H):
            np.testing.assert_allclose(
                np.asarray(out[:, h * hd:(h + 1) * hd]),
                want[:, h * hd:(h + 1) * hd], rtol=1e-2, atol=1e-2,
                err_msg=f"head {h}")

    @pytest.mark.parametrize("layout", ["contiguous", "zigzag"])
    def test_ring_flash_causal_under_shard_map(self, tpu_device, layout):
        # ring attention with the carry-form kernel and the Pallas ring
        # backward under a REAL sp=2 shard_map (varying-axes checker on),
        # forward and gradients, against the lax path, which masks by
        # position; q, k and v are random, so the layout is only what
        # the call states about their rows
        from brpc_tpu.tpu.mesh import make_mesh
        from brpc_tpu.tpu.ring import ring_attention

        if len(jax.devices()) < 2:
            pytest.skip("ring over sp=2 needs 2 devices (the lane's one "
                        "permitted skip on a one-chip machine)")
        mesh = make_mesh({"sp": 2}, devices_list=jax.devices()[:2])
        rng = np.random.default_rng(11)
        B, S, H, D = 2, 512, 4, 128
        q, k, v = (jnp.asarray(rng.normal(size=(B, S, H, D)) * 0.5,
                               dtype=jnp.float32) for _ in range(3))

        def loss(use_flash):
            def f(q, k, v):
                out = ring_attention(q, k, v, mesh, "sp", causal=True,
                                     use_flash=use_flash, layout=layout)
                return jnp.sum(jnp.sin(out)), out
            return jax.jit(jax.value_and_grad(f, argnums=(0, 1, 2),
                                              has_aux=True))

        (_, out_f), g_f = loss(True)(q, k, v)
        (_, out_l), g_l = loss(False)(q, k, v)
        np.testing.assert_allclose(np.asarray(out_f), np.asarray(out_l),
                                   rtol=2e-2, atol=2e-2)
        for a, b in zip(g_f, g_l):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=5e-2, atol=2e-2)


class TestPagedDecodeOnChip:
    """Decode attention over the pages in place (ISSUE 30): the compiled
    kernel against the gather body at the `chat-steady` cell's widths."""

    @pytest.mark.parametrize("rows,context", [(8, 1024), (16, 1024)])
    def test_decode_program_paged_against_gather(self, tpu_device, rows,
                                                 context):
        # the whole decode program both ways on one pool: d_model 2048,
        # 16 heads, block 16; 4 of the cell's 12 layers so that both
        # programs' pools fit beside each other (nothing is donated)
        from brpc_tpu.serving.model import ModelConfig, _decode_logits

        cfg = ModelConfig(vocab=49152, d_model=2048, n_heads=16, n_layers=4,
                          max_context=2048)
        bs, blocks, d = 16, 2048, cfg.d_model
        rng = np.random.default_rng(rows + context)
        key = jax.random.key(rows)

        def draw(i, shape, scale):
            return jax.random.normal(jax.random.fold_in(key, i), shape,
                                     jnp.float32) * scale

        params = {"embed": draw(0, (cfg.vocab, d), 0.5 / np.sqrt(cfg.vocab))}
        for l in range(cfg.n_layers):
            for j, (name, shape) in enumerate(
                    [("wqkv", (d, 3 * d)), ("wo", (d, d)),
                     ("w1", (d, 2 * d)), ("w2", (2 * d, d))]):
                params[f"{name}{l}"] = draw(
                    1 + 4 * l + j, shape, 0.5 / np.sqrt(shape[0]))
        pool_shape = (cfg.n_layers, (blocks + 1) * bs, d)
        kpool, vpool = draw(90, pool_shape, 0.7), draw(91, pool_shape, 0.7)
        # ragged rows as the cell has them: 50 .. context positions, two
        # padded rows, tables shuffled over the pool
        live = rows - 2
        positions = np.zeros(rows, np.int32)
        positions[:live] = rng.integers(49, context, size=live)
        positions[0], positions[1] = context - 1, bs - 1
        ids = rng.permutation(np.arange(1, blocks + 1))
        tables = np.zeros((rows, context // bs), np.int32)
        used = 0
        for b in range(live):
            n = positions[b] // bs + 1
            tables[b, :n] = ids[used:used + n]
            used += n
        tokens = rng.integers(1, cfg.vocab, size=rows).astype(np.int32)

        def program(paged):
            return jax.jit(lambda p, k, v, t, pos, bt: _decode_logits(
                cfg, p, k, v, t, pos, bt, rows, context, paged))

        args = (params, kpool, vpool, tokens, positions, tables)
        k_p, v_p, logits_p = program(True)(*args)
        k_g, v_g, logits_g = program(False)(*args)
        logit_gap = float(jnp.max(jnp.abs(logits_p - logits_g)[:live]))
        spread = float(jnp.std(logits_g[:live]))
        same = int(jnp.sum(jnp.argmax(logits_p, -1)[:live]
                           == jnp.argmax(logits_g, -1)[:live]))
        slots = (tables[np.arange(live), positions[:live] // bs] * bs
                 + positions[:live] % bs)
        kv_gap = max(float(jnp.max(jnp.abs(a[-1, slots] - b[-1, slots])))
                     for a, b in ((k_p, k_g), (v_p, v_g)))
        print(f"\npaged decode {rows} x {context}: worst logit difference "
              f"{logit_gap:.3e} (logits' deviation {spread:.3e}), last "
              f"layer's written K/V rows {kv_gap:.3e}, greedy tokens equal "
              f"{same}/{live}")
        # both paths round their operands to bfloat16, in different places
        assert logit_gap < 0.05 * spread + 1e-3, (logit_gap, spread)
        assert kv_gap < 5e-2
        # layer 0's rows are written before any attention: equal
        assert bool(jnp.all(k_p[0, slots] == k_g[0, slots]))

    def test_generate_counts_paged_launches(self, tpu_device):
        from brpc_tpu.proto import serving_pb2
        from brpc_tpu.rpc import Channel, ChannelOptions, Server, Stub
        from brpc_tpu.serving import (EngineConfig, KVCacheConfig,
                                      LlmServingService, ModelConfig,
                                      PagedKVCache, ServingEngine,
                                      TinyTransformer)

        cfg = ModelConfig(vocab=512, d_model=256, n_heads=2, n_layers=2,
                          max_context=512)
        kv = PagedKVCache(KVCacheConfig(block_size=16, num_blocks=128),
                          cfg.n_layers, cfg.kv_dim)
        model = TinyTransformer(cfg, kv)
        engine = ServingEngine(model, kv, EngineConfig(
            max_batch=4, token_budget=1024)).start()
        server = Server().add_service(LlmServingService(engine)) \
            .start("127.0.0.1:0")
        try:
            ch = Channel(ChannelOptions(protocol="trpc_std",
                                        timeout_ms=300000))
            ch.init(str(server.listen_endpoint()))
            stub = Stub(ch, serving_pb2.DESCRIPTOR
                        .services_by_name["LlmService"])
            resp = stub.Generate(serving_pb2.GenerateRequest(
                prompt_len=40, max_new_tokens=24))
            assert len(resp.tokens) == 24
            dec = engine.snapshot()["decode"]
            print(f"\ndecode counters after a Generate: {dec}")
            assert dec["decode_launches_paged"] > 0
            assert dec["decode_launches_gather"] == 0
            assert 0 < dec["decode_pages_live"] <= dec["decode_pages_bucket"]
        finally:
            server.stop()
            server.join(timeout=2)
            engine.stop()
        kv.assert_idle()
        model.close()


class TestDeviceLanesOnChip:
    def test_tpusocket_device_echo(self, tpu_device):
        from brpc_tpu.proto import echo_pb2
        from brpc_tpu.rpc import Channel, ChannelOptions, Controller, Stub

        ch = Channel(ChannelOptions(timeout_ms=120000)).init("tpu://0")
        stub = Stub(ch, echo_pb2.DESCRIPTOR.services_by_name["EchoService"])
        payload = bytes(range(256)) * 256  # 64KB through HBM
        r = stub.Echo(echo_pb2.EchoRequest(message="hw", payload=payload))
        assert r.message == "hw"
        assert r.payload == payload

    def test_device_store_on_chip(self, tpu_device):
        from brpc_tpu.tpu.device_lane import DeviceStore

        store = DeviceStore(tpu_device)
        blob = bytes(range(256)) * 1024
        h, n = store.put(blob)
        checksum, moved = store.pump(h, rounds=2)
        checksum2, _ = store.pump(h, rounds=5)
        assert checksum == checksum2  # copies preserve data
        assert store.get(h) == blob
