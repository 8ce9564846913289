"""TPU-layer tests on the virtual 8-device CPU mesh (SURVEY §4: the fake
cluster substrate — N virtual chips stand in for a pod the way N loopback
channels stand in for N servers in the reference)."""

import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from brpc_tpu.tpu import collective, mesh as meshlib
from brpc_tpu.tpu.ring import (LAYOUTS, full_attention_reference,
                               pair_schedule, ring_attention, shard_blocks,
                               shard_rows)


@pytest.fixture(scope="module")
def mesh8():
    return meshlib.make_mesh({"x": -1})


class TestMesh:
    def test_device_count(self):
        assert meshlib.device_count() == 8

    def test_make_mesh_infer(self):
        m = meshlib.make_mesh({"dp": 2, "tp": -1})
        assert m.shape == {"dp": 2, "tp": 4}

    def test_bad_mesh(self):
        with pytest.raises(ValueError):
            meshlib.make_mesh({"dp": 3})

    def test_endpoints(self):
        eps = meshlib.list_device_endpoints()
        assert len(eps) == 8
        assert all(e.is_tpu() for e in eps)
        assert meshlib.resolve_device(eps[3]).id == eps[3].device_ordinal


class TestCollectives:
    def test_all_reduce_matches_sum(self, mesh8):
        x = jnp.arange(16.0)
        out = collective.all_reduce(x, mesh8, "x")
        # each shard of 2 gets the sum over the axis of its position-mates
        expected = x.reshape(8, 2).sum(0)
        np.testing.assert_allclose(np.asarray(out).reshape(8, 2)[0], expected)

    def test_all_gather_identity(self, mesh8):
        x = jnp.arange(8.0)
        out = collective.all_gather(x, mesh8, "x")
        assert out.shape == (64,)
        np.testing.assert_allclose(np.asarray(out)[:8], np.arange(8.0))

    def test_reduce_scatter(self, mesh8):
        # 8 devices each contribute a [16] row; result = row-sum, scattered
        x = jnp.ones((8, 16))
        out = collective.reduce_scatter(x, mesh8, "x")
        assert out.shape == (16,)
        np.testing.assert_allclose(np.asarray(out), 8.0 * np.ones(16))

    def test_shift_rotates(self, mesh8):
        x = jnp.arange(8.0)
        out = collective.shift(x, mesh8, "x", offset=1)
        np.testing.assert_allclose(np.asarray(out), np.roll(np.arange(8.0), 1))

    def test_ring_all_reduce_equals_sum(self, mesh8):
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.normal(size=(8, 32)), dtype=jnp.float32)
        ring = np.asarray(collective.ring_all_reduce(x, mesh8, "x"))
        expected = np.asarray(x).sum(0)
        for row in ring:  # every device ends with the full sum
            np.testing.assert_allclose(row, expected, rtol=1e-5, atol=1e-6)

    def test_fanout_sum_merge(self, mesh8):
        fn = collective.fanout(lambda s: s * 2.0, mesh8, "x", merge="sum")
        x = jnp.ones((8,))
        out = fn(x)
        np.testing.assert_allclose(np.asarray(out), 16.0 * np.ones(8))

    def test_partition_stays_sharded(self, mesh8):
        fn = collective.partition(lambda s: s + 1.0, mesh8, "x")
        x = jnp.zeros((8,))
        np.testing.assert_allclose(np.asarray(fn(x)), np.ones(8))

    def test_all_to_all(self, mesh8):
        # [8, 8] sharded on dim0; swap shard ownership to dim1
        x = jnp.arange(64.0).reshape(8, 8)
        out = collective.all_to_all(x, mesh8, "x", split_axis=1, concat_axis=0)
        assert out.shape == (64, 1)


RING_SIZES = [1, 2, 4, 8]


def _ring_mesh(n):
    """sp = n, with what is left of the 8 devices on dp and tp."""
    dp = 2 if n <= 4 else 1
    tp = 2 if n <= 2 else 1
    return meshlib.make_mesh({"dp": dp, "sp": n, "tp": tp},
                             jax.devices()[:dp * n * tp])


def _qkv(seed, shape, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    return tuple(jnp.asarray(rng.normal(size=shape), dtype=dtype)
                 for _ in range(3))


def _ring_transfers(fn, *args):
    """(scope, element type) of every collective_permute in fn's lowering,
    in program order."""
    text = jax.jit(fn).lower(*args).as_text(debug_info=True)
    locs = dict(re.findall(r'^(#loc\d+) = loc\("([^"]*)"', text, flags=re.M))
    return [(locs[loc].rsplit("/", 1)[0], dtype) for dtype, loc in re.findall(
        r'"stablehlo\.collective_permute".*-> tensor<[\dx]*x(\w+)> '
        r'loc\((#loc\d+)\)', text)]


def _ring(q, k, v, m, axis, layout, **kw):
    """ring_attention on a sequence-order q, k, v whose rows are put into
    ``layout``'s order on the way in and back on the way out."""
    order = shard_rows(q.shape[1], m.shape[axis], layout)
    out = ring_attention(q[:, order], k[:, order], v[:, order], m, axis,
                         layout=layout, **kw)
    return out[:, np.argsort(order)]


class TestRingAttention:
    @pytest.mark.parametrize("layout", LAYOUTS)
    @pytest.mark.parametrize("causal", [False, True])
    @pytest.mark.parametrize("n", RING_SIZES)
    def test_matches_full_attention(self, n, causal, layout):
        m = meshlib.make_mesh({"x": n}, jax.devices()[:n])
        q, k, v = _qkv(1, (2, 32, 4, 16))
        out_ring = _ring(q, k, v, m, "x", layout, causal=causal)
        out_full = full_attention_reference(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(out_ring), np.asarray(out_full),
                                   rtol=2e-4, atol=2e-5)

    @pytest.mark.parametrize("layout", LAYOUTS)
    def test_composes_with_dp_tp(self, layout):
        m = meshlib.make_mesh({"dp": 2, "sp": 2, "tp": 2})
        q, k, v = _qkv(2, (2, 16, 4, 8))
        out = _ring(q, k, v, m, "sp", layout, causal=True, batch_axis="dp",
                    head_axis="tp")
        ref = full_attention_reference(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-4, atol=2e-5)

    @pytest.mark.parametrize("layout", LAYOUTS)
    @pytest.mark.parametrize("causal", [False, True])
    @pytest.mark.parametrize("n", RING_SIZES)
    def test_flash_kernel_inside_ring(self, n, causal, layout):
        # VERDICT r2 #5: the carry-form Pallas kernel accumulates ACROSS
        # hops; the lax path, which masks by position and knows nothing
        # of the pair schedule, is the oracle
        m = meshlib.make_mesh({"x": n}, jax.devices()[:n])
        q, k, v = _qkv(3, (2, 32, 4, 16))
        out_flash = _ring(q, k, v, m, "x", layout, causal=causal,
                          use_flash=True)
        out_lax = _ring(q, k, v, m, "x", layout, causal=causal)
        out_full = full_attention_reference(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(out_flash),
                                   np.asarray(out_lax),
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(np.asarray(out_flash),
                                   np.asarray(out_full),
                                   rtol=2e-4, atol=2e-5)

    @pytest.mark.parametrize("layout", LAYOUTS)
    def test_flash_ring_composes_with_dp_tp(self, layout):
        m = meshlib.make_mesh({"dp": 2, "sp": 2, "tp": 2})
        q, k, v = _qkv(4, (2, 16, 4, 8))
        out = _ring(q, k, v, m, "sp", layout, causal=True, batch_axis="dp",
                    head_axis="tp", use_flash=True)
        ref = full_attention_reference(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-4, atol=2e-5)

    @pytest.mark.parametrize("layout", LAYOUTS)
    @pytest.mark.parametrize("causal", [False, True])
    @pytest.mark.parametrize("n", RING_SIZES)
    def test_flash_ring_gradients_match_reference(self, n, causal, layout):
        # VERDICT r3 #3: the ring-flash path must be trainable — its
        # custom VJP runs the Pallas flash-backward kernels per hop and
        # rotates dk/dv home around the ring
        m = _ring_mesh(n)
        q, k, v = _qkv(7, (2, 32, 4, 16))

        def loss_flash(q, k, v):
            o = _ring(q, k, v, m, "sp", layout, causal=causal,
                      batch_axis="dp", head_axis="tp", use_flash=True,
                      block_q=16, block_k=16)
            return jnp.sum(jnp.sin(o))

        def loss_ref(q, k, v):
            return jnp.sum(jnp.sin(
                full_attention_reference(q, k, v, causal=causal)))

        g = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=5e-4, atol=5e-5)

    @pytest.mark.parametrize("layout", LAYOUTS)
    @pytest.mark.parametrize("dtype,narrow", [(jnp.bfloat16, "bf16"),
                                              (jnp.float32, "f32")])
    def test_ring_transfer_counts_and_dtypes(self, dtype, narrow, layout):
        # the hop schedule is fixed when the program is traced, so it is
        # read from the program: n - 1 rotations of K and V a pass, n of
        # dK and dV, of which the first and the last carry the kernel's
        # dtype (float32 inputs: the cast is the identity). The same in
        # both layouts: a zigzag hop's dK/dV is still ONE kernel call's
        # output, so no rotation had to widen
        n = 4
        m = meshlib.make_mesh({"sp": n}, jax.devices()[:n])
        x = jax.ShapeDtypeStruct((1, 64, 2, 16), dtype)

        def f(q, k, v):
            return ring_attention(q, k, v, m, "sp", causal=True,
                                  use_flash=True, block_q=16, block_k=16,
                                  layout=layout)

        def loss(q, k, v):
            return jnp.sum(f(q, k, v).astype(jnp.float32))

        fwd = _ring_transfers(f, x, x, x)
        assert fwd == [("ring_fwd_hop/ring_kv_ppermute", narrow)] * (
            2 * (n - 1))
        both = _ring_transfers(jax.grad(loss, argnums=(0, 1, 2)), x, x, x)
        assert both[:len(fwd)] == fwd
        bwd = both[len(fwd):]
        assert len(bwd) == 2 * (n - 1) + 2 * n
        assert [t for t in bwd if t[0].endswith("ring_kv_ppermute")] == [
            ("ring_bwd_hop/ring_kv_ppermute", narrow)] * (2 * (n - 1))
        assert [d for s, d in bwd if s == "ring_bwd_hop/ring_dkv_ppermute"
                ] == [narrow] * 2 + ["f32"] * (2 * (n - 2)) + [narrow] * 2

    @pytest.mark.parametrize("n", [1, 2, 4, 8])
    def test_pair_schedule_contiguous_is_d_plus_one_of_n(self, n):
        # host only: device d runs the home triangle and one full
        # shard-by-shard tile for each of the d devices before it
        area = _live_area(n, "contiguous")
        for d in range(n):
            assert area[0, d] == 0.5
            assert [area[i, d] for i in range(1, n)] == [
                float(d >= i) for i in range(1, n)]
            assert np.count_nonzero(area[:, d]) == d + 1

    @pytest.mark.parametrize("n", [1, 2, 4, 8])
    def test_pair_schedule_zigzag_is_level(self, n):
        # host only: equal live rows x keys on every device at every hop;
        # at home two triangles and a full block pair, visiting two full
        # pairs (half a shard-by-shard tile), none masked and none dead
        # inside a call
        area = _live_area(n, "zigzag")
        assert (area[0] == 0.5).all() and (area[1:] == 0.5).all()
        sched = pair_schedule(n, "zigzag", causal=True)
        for d in range(n):
            assert set(sched[0][d]) == {(0, 0, "diag"), (1, 0, "full"),
                                        (1, 1, "diag")}
            for i in range(1, n):
                want = ({(0, 0, "full"), (1, 0, "full")} if d >= i
                        else {(1, 0, "full"), (1, 1, "full")})
                assert set(sched[i][d]) == want
        # every block pair of the sequence is met exactly once
        blocks = shard_blocks(n, "zigzag")
        met = sorted((blocks[d][a], blocks[(d - i) % n][b])
                     for i in range(n) for d in range(n)
                     for a, b, _ in sched[i][d])
        assert met == [(a, b) for a in range(2 * n) for b in range(a + 1)]

    @pytest.mark.parametrize("layout", LAYOUTS)
    def test_pair_schedule_without_mask_is_everything(self, layout):
        nb = len(shard_blocks(4, layout)[0])
        for hop in pair_schedule(4, layout, causal=False):
            for pairs in hop:
                assert set(pairs) == {(a, b, "full") for a in range(nb)
                                      for b in range(nb)}

    def test_shard_rows_round_trip_and_refusals(self):
        assert shard_rows(16, 4).tolist() == list(range(16))
        assert shard_rows(16, 2, "zigzag").tolist() == [
            0, 1, 2, 3, 12, 13, 14, 15, 4, 5, 6, 7, 8, 9, 10, 11]
        with pytest.raises(ValueError):
            shard_rows(12, 4, "zigzag")
        with pytest.raises(ValueError):
            shard_blocks(4, "striped")
        m = meshlib.make_mesh({"x": 4}, jax.devices()[:4])
        q, k, v = _qkv(5, (1, 12, 2, 8))
        with pytest.raises(ValueError):
            ring_attention(q, k, v, m, "x", causal=True, layout="zigzag")


def _live_area(n, layout):
    """[hop, device] live rows x keys, in shard-by-shard tiles (a
    triangle counts half its block pair)."""
    nb = len(shard_blocks(n, layout)[0])
    return np.array([[sum(0.5 if kind == "diag" else 1.0
                          for _, _, kind in pairs) / nb ** 2
                      for pairs in hop]
                     for hop in pair_schedule(n, layout, causal=True)])


class TestPallasOps:
    def test_rmsnorm_matches_reference(self):
        from brpc_tpu.tpu.pallas_ops import rmsnorm, rmsnorm_reference

        rng = np.random.default_rng(3)
        x = jnp.asarray(rng.normal(size=(4, 32, 128)), dtype=jnp.float32)
        w = jnp.asarray(rng.normal(size=(128,)), dtype=jnp.float32)
        out = rmsnorm(x, w)
        ref = rmsnorm_reference(x, w)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-6)

    def test_rmsnorm_ragged_rows(self):
        from brpc_tpu.tpu.pallas_ops import rmsnorm, rmsnorm_reference

        x = jnp.ones((7, 64))  # N not divisible by block_rows
        w = jnp.ones((64,))
        np.testing.assert_allclose(
            np.asarray(rmsnorm(x, w, block_rows=4)),
            np.asarray(rmsnorm_reference(x, w)), rtol=1e-5)

    def test_rmsnorm_gradients_match_reference(self):
        from brpc_tpu.tpu.pallas_ops import rmsnorm, rmsnorm_reference

        rng = np.random.default_rng(9)
        x = jnp.asarray(rng.normal(size=(4, 32, 128)), dtype=jnp.float32)
        w = jnp.asarray(rng.normal(size=(128,)), dtype=jnp.float32)
        gx, gw = jax.grad(
            lambda x, w: jnp.sum(jnp.sin(rmsnorm(x, w))),
            argnums=(0, 1))(x, w)
        rx, rw = jax.grad(
            lambda x, w: jnp.sum(jnp.sin(rmsnorm_reference(x, w))),
            argnums=(0, 1))(x, w)
        np.testing.assert_allclose(np.asarray(gx), np.asarray(rx),
                                   rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(np.asarray(gw), np.asarray(rw),
                                   rtol=1e-4, atol=1e-5)


class TestTpuSocket:
    """The transport graft: RPC whose wire is the device DMA engine."""

    def test_echo_through_device(self):
        from brpc_tpu.proto import echo_pb2
        from brpc_tpu.rpc import Channel, Stub

        ch = Channel().init("tpu://localhost/0")
        stub = Stub(ch, echo_pb2.DESCRIPTOR.services_by_name["EchoService"])
        payload = bytes(range(256)) * 64
        resp = stub.Echo(echo_pb2.EchoRequest(message="via-hbm",
                                              payload=payload))
        assert resp.message == "via-hbm"
        assert resp.payload == payload

    def test_attachment_rides_device(self):
        from brpc_tpu.proto import echo_pb2
        from brpc_tpu.rpc import Channel, Controller, Stub

        ch = Channel().init("tpu://localhost/1")
        stub = Stub(ch, echo_pb2.DESCRIPTOR.services_by_name["EchoService"])
        cntl = Controller()
        cntl.request_attachment = b"DEVICE-ATTACH"
        stub.Echo(echo_pb2.EchoRequest(message="a"), controller=cntl)
        assert cntl.response_attachment == b"DEVICE-ATTACH"

    def test_unknown_device_method(self):
        from brpc_tpu.proto import echo_pb2
        from brpc_tpu.rpc import Channel, MethodDescriptor, RpcError, errors

        ch = Channel().init("tpu://localhost/0")
        md = MethodDescriptor("NoSvc", "NoMeth",
                              echo_pb2.EchoRequest, echo_pb2.EchoResponse)
        with pytest.raises(RpcError) as ei:
            ch.call_method(md, echo_pb2.EchoRequest(message="x"))
        assert ei.value.error_code == errors.ENOMETHOD

    def test_custom_device_method(self):
        import jax.numpy as jnp

        from brpc_tpu.proto import echo_pb2
        from brpc_tpu.rpc import Channel, MethodDescriptor
        from brpc_tpu.tpu.tpusocket import register_device_method
        from brpc_tpu.rpc import errors as err

        def reverse_handler(device, meta, payload, attachment):
            req = echo_pb2.EchoRequest()
            req.ParseFromString(payload)
            arr = jnp.asarray(bytearray(req.payload), dtype=jnp.uint8)
            rev = bytes(np.asarray(arr[::-1]))
            resp = echo_pb2.EchoResponse(message=req.message[::-1], payload=rev)
            return err.OK, resp.SerializeToString(), b""

        register_device_method("RevService", "Reverse", reverse_handler)
        ch = Channel().init("tpu://localhost/2")
        md = MethodDescriptor("RevService", "Reverse",
                              echo_pb2.EchoRequest, echo_pb2.EchoResponse)
        resp = ch.call_method(
            md, echo_pb2.EchoRequest(message="abc", payload=b"1234"))
        assert resp.message == "cba" and resp.payload == b"4321"


class TestTrain:
    def test_single_device_forward(self):
        from brpc_tpu.tpu import train

        cfg = train.ModelConfig(vocab=64, d_model=32, n_heads=4, n_layers=2,
                                d_ff=64, max_seq=16)
        params = train.init_params(jax.random.PRNGKey(0), cfg)
        tokens = jnp.zeros((2, 16), dtype=jnp.int32)
        logits = train.forward(params, tokens, cfg)
        assert logits.shape == (2, 16, 64)

    def test_sharded_train_step_runs_and_learns(self):
        from brpc_tpu.tpu import train

        m = meshlib.make_mesh({"dp": 2, "sp": 2, "tp": 2})
        cfg = train.ModelConfig(vocab=64, d_model=32, n_heads=4, n_layers=2,
                                d_ff=64, max_seq=16)
        params = train.init_params(jax.random.PRNGKey(0), cfg)
        step, pshard, bshard = train.make_train_step(cfg, m, lr=1e-2)
        params = jax.device_put(params, pshard)
        batch = train.demo_batch(jax.random.PRNGKey(1), cfg, batch=4, seq=16)
        batch = jax.device_put(batch, bshard)
        losses = []
        for _ in range(5):
            params, loss = step(params, batch)
            losses.append(float(loss))
        assert losses[-1] < losses[0]  # actually learning

    def test_sharded_forward_matches_unsharded(self):
        from brpc_tpu.tpu import train

        m = meshlib.make_mesh({"dp": 2, "sp": 2, "tp": 2})
        cfg = train.ModelConfig(vocab=64, d_model=32, n_heads=4, n_layers=2,
                                d_ff=64, max_seq=16)
        params = train.init_params(jax.random.PRNGKey(0), cfg)
        tokens = jax.random.randint(jax.random.PRNGKey(2), (2, 16), 0, 64)
        ref = train.forward(params, tokens, cfg)

        with m:
            sharded = jax.jit(
                lambda p, t: train.forward(p, t, cfg, mesh=m))(params, tokens)
        np.testing.assert_allclose(np.asarray(sharded), np.asarray(ref),
                                   rtol=5e-4, atol=5e-5)


    @pytest.mark.parametrize("shape", [(2, 4, 1), (1, 4, 2), (2, 2, 2),
                                       (1, 8, 1)],
                             ids=lambda s: "dp%d_sp%d_tp%d" % s)
    def test_mesh_loss_is_the_single_device_loss(self, mesh8, shape):
        """The mesh step takes the batch in sequence order and puts its
        rows into the ring's zigzag order itself: loss and gradients on
        the 8 devices equal the single-device ones on the SAME batch, and
        ``forward`` hands its logits back in sequence order."""
        from jax.sharding import Mesh

        from brpc_tpu.tpu import train

        m = Mesh(mesh8.devices.reshape(shape), ("dp", "sp", "tp"))
        cfg = train.ModelConfig(vocab=64, d_model=32, n_heads=4, n_layers=2,
                                d_ff=64, max_seq=32)
        assert train._ring_layout(32, m, True)[0] == "zigzag"
        params = train.init_params(jax.random.PRNGKey(0), cfg)
        batch = train.demo_batch(jax.random.PRNGKey(1), cfg, batch=2, seq=32)
        ref, gref = jax.value_and_grad(train.loss_fn)(params, batch, cfg)
        out, g = jax.jit(jax.value_and_grad(
            lambda p, b: train.loss_fn(p, b, cfg, m)))(params, batch)
        np.testing.assert_allclose(float(out), float(ref), rtol=2e-6)
        for a, b in zip(jax.tree_util.tree_leaves(g),
                        jax.tree_util.tree_leaves(gref)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=5e-4, atol=2e-6)
        logits = jax.jit(lambda p, t: train.forward(p, t, cfg, mesh=m))(
            params, batch[0])
        np.testing.assert_allclose(
            np.asarray(logits),
            np.asarray(train.forward(params, batch[0], cfg)),
            rtol=5e-4, atol=5e-5)

    def test_ring_layout_falls_back_where_zigzag_cannot_cut(self, mesh8):
        from jax.sharding import Mesh

        from brpc_tpu.tpu import train

        m = Mesh(mesh8.devices.reshape(2, 4, 1), ("dp", "sp", "tp"))
        assert train._ring_layout(32, None, True) == ("contiguous", None)
        assert train._ring_layout(32, m, False) == ("contiguous", None)
        assert train._ring_layout(12, m, True) == ("contiguous", None)
        layout, order = train._ring_layout(16, m, True)
        assert layout == "zigzag" and sorted(order) == list(range(16))


class TestFlashAttention:
    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_reference(self, causal):
        import jax

        from brpc_tpu.tpu.pallas_ops import (attention_reference,
                                             flash_attention_mha)

        key = jax.random.PRNGKey(0)
        kq, kk, kv = jax.random.split(key, 3)
        S, D = 256, 64
        q = jax.random.normal(kq, (S, D), dtype=jnp.float32)
        k = jax.random.normal(kk, (S, D), dtype=jnp.float32)
        v = jax.random.normal(kv, (S, D), dtype=jnp.float32)
        # one sequence, one head
        out = flash_attention_mha(q[None, None], k[None, None],
                                  v[None, None], causal=causal, block_q=64,
                                  block_k=64, interpret=True)[0, 0]
        ref = attention_reference(q, k, v, causal=causal)
        assert jnp.allclose(out, ref, atol=2e-3), float(
            jnp.abs(out - ref).max())

    def test_multi_head(self):
        import jax

        from brpc_tpu.tpu.pallas_ops import (attention_reference,
                                             flash_attention_mha)

        key = jax.random.PRNGKey(1)
        B, H, S, D = 2, 4, 128, 32
        q, k, v = (jax.random.normal(kk, (B, H, S, D), dtype=jnp.float32)
                   for kk in jax.random.split(key, 3))
        out = flash_attention_mha(q, k, v, causal=True, block_q=64,
                                  block_k=64, interpret=True)
        for b in range(B):
            for h in range(H):
                ref = attention_reference(q[b, h], k[b, h], v[b, h],
                                          causal=True)
                assert jnp.allclose(out[b, h], ref, atol=2e-3)

    @pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
    @pytest.mark.parametrize("s", [128, 384, 1024])
    @pytest.mark.parametrize("per", [1, 4, 16])
    def test_grouped_heads_read_their_kv_head_in_place(self, per, s, dtype):
        """K/V with fewer heads than q: query head ``n`` against K/V head
        ``n // per``, each against the O(S^2) reference on the same
        operands; nothing is repeated on the way in."""
        import jax

        from brpc_tpu.tpu.pallas_ops import (attention_reference,
                                             flash_attention_mha)

        g, d = (2 if s < 1024 else 1), 32
        dt = jnp.dtype(dtype)
        kq, kk, kv = jax.random.split(jax.random.PRNGKey(per * s), 3)
        q = jax.random.normal(kq, (1, g * per, s, d), jnp.float32).astype(dt)
        k = jax.random.normal(kk, (1, g, s, d), jnp.float32).astype(dt)
        v = jax.random.normal(kv, (1, g, s, d), jnp.float32).astype(dt)
        out = flash_attention_mha(q, k, v, causal=True, interpret=True)
        assert out.shape == q.shape and out.dtype == dt
        tol = 2e-3 if dtype == "float32" else 3e-2
        for n in range(g * per):
            ref = attention_reference(q[0, n], k[0, n // per], v[0, n // per],
                                      causal=True)
            np.testing.assert_allclose(
                np.asarray(out[0, n], np.float32),
                np.asarray(ref, np.float32), atol=tol)

    @pytest.mark.parametrize("causal,bq,bk", [(True, 64, 64), (True, 64, 32),
                                              (False, 64, 64)],
                             ids=["folded", "causal_grid", "full_grid"])
    def test_grouped_forward_is_the_ungrouped_one_bit_for_bit(self, causal,
                                                              bq, bk):
        """The index map is all that differs: over K/V repeated a query
        head the ungrouped program gives the same bits, in every forward of
        the family, two heads a step (one K/V head under both, or two whole
        groups) included; and a K/V head a query head IS the ungrouped
        program, equal to the same call one head at a time."""
        import jax

        from brpc_tpu.tpu.pallas_ops import flash_attention_mha

        kq, kk, kv = jax.random.split(jax.random.PRNGKey(3), 3)
        q = jax.random.normal(kq, (2, 4, 128, 32), jnp.float32)
        for g in (4, 2, 1):
            k = jax.random.normal(kk, (2, g, 128, 32), jnp.float32)
            v = jax.random.normal(kv, (2, g, 128, 32), jnp.float32)
            kw = dict(causal=causal, block_q=bq, block_k=bk, interpret=True)
            got = flash_attention_mha(q, k, v, **kw)
            rep = flash_attention_mha(q, jnp.repeat(k, 4 // g, axis=1),
                                      jnp.repeat(v, 4 // g, axis=1), **kw)
            assert np.array_equal(np.asarray(got), np.asarray(rep))
        one = jax.vmap(jax.vmap(lambda a, b, c: flash_attention_mha(
            a[None, None], b[None, None], c[None, None], causal=causal,
            block_q=bq, block_k=bk, interpret=True)[0, 0]))(
            q, k.repeat(4, axis=1), v.repeat(4, axis=1))
        assert np.array_equal(np.asarray(got), np.asarray(one))

    def test_grouped_heads_must_divide(self):
        from brpc_tpu.tpu.pallas_ops import flash_attention_mha

        q, k = jnp.zeros((1, 4, 64, 32)), jnp.zeros((1, 3, 64, 32))
        with pytest.raises(ValueError):
            flash_attention_mha(q, k, k, causal=True, interpret=True)

    def test_block_misalignment_rejected(self):
        import jax

        from brpc_tpu.tpu.pallas_ops import flash_attention_mha

        q = jnp.zeros((1, 1, 100, 32))
        with pytest.raises(ValueError):
            flash_attention_mha(q, q, q, block_q=64, block_k=64,
                                interpret=True)

    @pytest.mark.parametrize("causal", [False, True])
    def test_mha_gradients_match_reference(self, causal):
        # the Pallas backward kernels (dq / dkv) against AD through the
        # O(S^2) reference
        import jax

        from brpc_tpu.tpu.pallas_ops import (attention_reference,
                                             flash_attention_mha)

        key = jax.random.PRNGKey(5)
        B, H, S, D = 2, 3, 128, 32
        q, k, v = (jax.random.normal(kk, (B, H, S, D), dtype=jnp.float32)
                   for kk in jax.random.split(key, 3))

        def ref(q, k, v):
            f = lambda q1, k1, v1: attention_reference(q1, k1, v1,
                                                       causal=causal)
            return jax.vmap(jax.vmap(f))(q, k, v)

        def loss_f(q, k, v):
            return jnp.sum(jnp.sin(flash_attention_mha(
                q, k, v, causal=causal, block_q=64, block_k=64,
                interpret=True)))

        def loss_r(q, k, v):
            return jnp.sum(jnp.sin(ref(q, k, v)))

        g = jax.grad(loss_f, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss_r, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=5e-4, atol=5e-5)

    def test_flash_attention_on_hardware(self):
        """Exercise the NATIVE Mosaic lowering (scratch shapes, tiling) —
        interpret mode can hide hardware constraints. bf16 MXU matmuls
        give ~1e-2 error vs the fp32 reference at D=128."""
        import jax

        if jax.default_backend() != "tpu":
            pytest.skip("no TPU backend")
        from brpc_tpu.tpu.pallas_ops import (attention_reference,
                                             flash_attention_mha)

        key = jax.random.PRNGKey(2)
        q, k, v = (jax.random.normal(kk, (256, 128), dtype=jnp.float32)
                   for kk in jax.random.split(key, 3))
        out = flash_attention_mha(q[None, None], k[None, None],
                                  v[None, None], causal=True,
                                  interpret=False)[0, 0]
        ref = attention_reference(q, k, v, causal=True)
        assert jnp.allclose(out, ref, atol=2e-2), float(
            jnp.abs(out - ref).max())


class TestFlashInModel:
    def test_forward_matches_reference_attention(self):
        import jax

        from brpc_tpu.tpu import train

        cfg_ref = train.ModelConfig(vocab=64, d_model=64, n_heads=2,
                                    n_layers=2, d_ff=128, max_seq=128,
                                    use_flash_attention=False)
        cfg_flash = train.ModelConfig(vocab=64, d_model=64, n_heads=2,
                                      n_layers=2, d_ff=128, max_seq=128,
                                      use_flash_attention=True)
        params = train.init_params(jax.random.PRNGKey(0), cfg_ref)
        tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 128), 0, 64)
        ref = train.forward(params, tokens, cfg_ref)
        out = train.forward(params, tokens, cfg_flash)
        assert jnp.allclose(out, ref, atol=3e-3), float(
            jnp.abs(out - ref).max())

    def test_train_step_grads_through_flash(self):
        # the default config is kernels-on (VERDICT r3 #3): a full
        # value_and_grad train step must flow through the Pallas custom
        # VJPs and match the XLA-attention baseline's gradients
        import jax

        from brpc_tpu.tpu import train

        base = dict(vocab=64, d_model=64, n_heads=2, n_layers=2,
                    d_ff=128, max_seq=128)
        cfg_on = train.ModelConfig(**base, use_flash_attention=True)
        cfg_off = train.ModelConfig(**base, use_flash_attention=False)
        params = train.init_params(jax.random.PRNGKey(0), cfg_on)
        batch = train.demo_batch(jax.random.PRNGKey(1), cfg_on, 2, 128)
        loss_on, g_on = jax.value_and_grad(train.loss_fn)(params, batch,
                                                          cfg_on)
        loss_off, g_off = jax.value_and_grad(train.loss_fn)(params, batch,
                                                            cfg_off)
        assert jnp.allclose(loss_on, loss_off, rtol=1e-4)
        flat_on = jax.tree_util.tree_leaves(g_on)
        flat_off = jax.tree_util.tree_leaves(g_off)
        for a, b in zip(flat_on, flat_off):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=5e-3, atol=5e-4)


class TestFusedXent:
    def test_matches_reference(self):
        import jax

        from brpc_tpu.tpu.pallas_ops import (softmax_xent,
                                             softmax_xent_reference)

        key = jax.random.PRNGKey(3)
        logits = jax.random.normal(key, (512, 1024), dtype=jnp.float32) * 3
        targets = jax.random.randint(jax.random.PRNGKey(4), (512,), 0, 1024)
        out = softmax_xent(logits, targets, interpret=True)
        ref = softmax_xent_reference(logits, targets)
        assert jnp.allclose(out, ref, atol=1e-4), (float(out), float(ref))

    def test_odd_row_counts_supported(self):
        import jax

        from brpc_tpu.tpu.pallas_ops import (softmax_xent,
                                             softmax_xent_reference)

        logits = jax.random.normal(jax.random.PRNGKey(5), (100, 64)) * 2
        targets = jax.random.randint(jax.random.PRNGKey(6), (100,), 0, 64)
        out = softmax_xent(logits, targets, block_rows=64, interpret=True)
        assert jnp.allclose(out, softmax_xent_reference(logits, targets),
                            atol=1e-4)

    def test_fused_xent_in_loss(self):
        import jax

        from brpc_tpu.tpu import train

        cfg = train.ModelConfig(vocab=64, d_model=32, n_heads=2, n_layers=1,
                                d_ff=64, max_seq=32)
        cfg_fused = train.ModelConfig(vocab=64, d_model=32, n_heads=2,
                                      n_layers=1, d_ff=64, max_seq=32,
                                      use_fused_xent=True)
        params = train.init_params(jax.random.PRNGKey(0), cfg)
        batch = train.demo_batch(jax.random.PRNGKey(1), cfg, 2, 32)
        ref = train.loss_fn(params, batch, cfg)
        out = train.loss_fn(params, batch, cfg_fused)
        assert jnp.allclose(out, ref, atol=1e-5), (float(out), float(ref))

    def test_fused_xent_gradients_match(self):
        import jax

        from brpc_tpu.tpu.pallas_ops import (softmax_xent,
                                             softmax_xent_reference)

        logits = jax.random.normal(jax.random.PRNGKey(7), (64, 128)) * 2
        targets = jax.random.randint(jax.random.PRNGKey(8), (64,), 0, 128)
        g_fused = jax.grad(lambda x: softmax_xent(x, targets))(logits)
        g_ref = jax.grad(
            lambda x: softmax_xent_reference(x, targets))(logits)
        assert jnp.allclose(g_fused, g_ref, atol=1e-5), float(
            jnp.abs(g_fused - g_ref).max())

    def test_fused_xent_train_step(self):
        import jax

        from brpc_tpu.tpu import train

        cfg = train.ModelConfig(vocab=64, d_model=32, n_heads=2, n_layers=1,
                                d_ff=64, max_seq=32, use_fused_xent=True)
        params = train.init_params(jax.random.PRNGKey(0), cfg)
        batch = train.demo_batch(jax.random.PRNGKey(1), cfg, 2, 32)
        params2, loss = train.sgd_train_step(params, batch, cfg)
        assert jnp.isfinite(loss)  # grad through the kernel works
