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


class TestServingAndRingOnChip:
    """The two paths no hardware test had, and the chip refused (PR 21)."""

    @pytest.mark.parametrize("S", [16, 128, 384])
    def test_serving_prefill_attention_layout(self, tpu_device, S):
        # serving's layout and dtype: (S, H, hd) float32, heads in the
        # MIDDLE — through the one spelling both prefill programs share
        from brpc_tpu.serving.model import _prefill_attention

        rng = np.random.default_rng(S)
        H, hd = 16, 128
        q, k, v = (jnp.asarray(rng.normal(size=(S, H, hd)) * 0.5,
                               dtype=jnp.float32) for _ in range(3))
        flash = jax.jit(lambda q, k, v: _prefill_attention(q, k, v, True))
        ref = jax.jit(lambda q, k, v: _prefill_attention(q, k, v, False))
        out = flash(q, k, v)
        assert out.shape == (S, H, hd) and out.dtype == jnp.float32
        # the reference einsum runs at the TPU's default matmul precision
        # (bf16 passes); the kernel's float32 dots do not
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref(q, k, v)),
                                   rtol=2e-2, atol=2e-2)

    def test_ring_flash_causal_under_shard_map(self, tpu_device):
        # ring attention with the carry-form kernel and the Pallas ring
        # backward under a REAL sp=2 shard_map (varying-axes checker on),
        # forward and gradients, against the lax path
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
                                     use_flash=use_flash)
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
