"""The selective-scan kernel (``pallas_ops.ssm_scan``, interpreted on the
CPU: the same kernel body the chip compiles) against the XLA form it
replaced (``ssm_scan_reference``) and against a plain row-by-row numpy
recurrence, at tiny widths; and the seam the benchmark's planted fault
(``benchmark/tests/broken_jamba.py scan_from_zero``) holds on to:
``jamba_model.ssm_scan`` is a module-level name the chunk program calls.

Tolerance: all three compute ``exp(dt a) * s + (dt u) b`` in float32; the
kernel and numpy in row order, the XLA form as a tree of products over 128
rows, so they differ by float32 rounding of sums of order 1: 2e-5 of the
largest value.
"""

import inspect

import jax.numpy as jnp
import numpy as np
import pytest

from brpc_tpu.serving import hybrid_model, jamba_model
from brpc_tpu.tpu import pallas_ops

N = 16
TOL = 2e-5


def _inputs(rows, di, seed=0, with_s0=True):
    r = np.random.RandomState(seed)
    f = np.float32
    dt = np.log1p(np.exp(r.randn(rows, di) - 3.0)).astype(f)     # ~5e-2
    u = r.randn(rows, di).astype(f)
    bm, cm = r.randn(rows, N).astype(f), r.randn(rows, N).astype(f)
    a = -(np.arange(1.0, N + 1)[:, None]
          * (1 + 0.2 * r.rand(N, di))).astype(f)
    s0 = (0.3 * r.randn(N, di)).astype(f) if with_s0 else None
    return dt, u, bm, cm, a, s0


def _numpy_scan(dt, u, bm, cm, a, s0):
    """Row by row, float32, the docstring's recurrence."""
    s = np.zeros_like(a) if s0 is None else s0.copy()
    ys = np.empty_like(dt)
    for t in range(len(dt)):
        s = np.exp(dt[t][None, :] * a) * s \
            + (dt[t] * u[t])[None, :] * bm[t][:, None]
        ys[t] = np.sum(s * cm[t][:, None], axis=0)
    return s, ys


def _close(got, want):
    got, want = np.asarray(got), np.asarray(want)
    assert np.isfinite(got).all()
    assert np.max(np.abs(got - want)) <= TOL * max(1.0, np.max(np.abs(want)))


@pytest.mark.parametrize("with_s0", [False, True], ids=["from_zero", "s0"])
@pytest.mark.parametrize("rows", [128, 384, 1536, 2048])
def test_kernel_equals_the_xla_form_and_the_row_by_row_recurrence(rows,
                                                                  with_s0):
    args = _inputs(rows, 128, seed=rows, with_s0=with_s0)
    s_end, y = pallas_ops.ssm_scan(*args)
    assert s_end.shape == (N, 128) and y.shape == (rows, 128)
    assert s_end.dtype == y.dtype == jnp.float32
    for want in (pallas_ops.ssm_scan_reference(*args), _numpy_scan(*args)):
        _close(s_end, want[0])
        _close(y, want[1])


@pytest.mark.parametrize("rows,di", [
    (256, 96),       # no whole lane row: the channel axis whole
    (128, 160),      # the same, wider than one
    (384, 384),      # 3 lane rows in one block
    (1024, 2048),    # 4 row blocks: the state crosses them
    (192, 16384),    # 128 lane rows a block, 6 row blocks of the least rows
    (48, 128),       # fewer rows than a block
    (37, 128),       # a row count nothing divides
])
def test_blocks_come_from_the_shapes(rows, di):
    args = _inputs(rows, di, seed=di)
    s_end, y = pallas_ops.ssm_scan(*args)
    want = _numpy_scan(*args)
    _close(s_end, want[0])
    _close(y, want[1])


@pytest.mark.parametrize("rows,live", [(128, 70), (512, 300), (256, 1),
                                       (128, 0)])
def test_rows_with_dt_zero_leave_the_state_as_it_was(rows, live):
    """How the callers mask pads: decay 1, drive 0. The state after a block
    of pad rows at the end is the state after the last live row (the given
    state, bit for bit, where every row is a pad), and the pads' y is
    finite."""
    dt, u, bm, cm, a, s0 = _inputs(rows, 128, seed=live)
    dt[live:] = 0.0
    u[live:] = 1e6           # whatever a pad row carries
    s_end, y = pallas_ops.ssm_scan(dt, u, bm, cm, a, s0)
    assert np.isfinite(np.asarray(y)).all()
    if not live:
        np.testing.assert_array_equal(np.asarray(s_end), s0)
        return
    want = _numpy_scan(dt[:live], u[:live], bm[:live], cm[:live], a, s0)
    _close(s_end, want[0])
    _close(y[:live], want[1])


@pytest.mark.parametrize("cut", [128, 640])
def test_two_chunks_chained_through_s_end_equal_one_scan(cut):
    dt, u, bm, cm, a, s0 = _inputs(768, 128, seed=cut)
    whole_s, whole_y = pallas_ops.ssm_scan(dt, u, bm, cm, a, s0)
    mid, y1 = pallas_ops.ssm_scan(dt[:cut], u[:cut], bm[:cut], cm[:cut], a,
                                  s0)
    end, y2 = pallas_ops.ssm_scan(dt[cut:], u[cut:], bm[cut:], cm[cut:], a,
                                  mid)
    np.testing.assert_array_equal(np.asarray(end), np.asarray(whole_s))
    np.testing.assert_array_equal(np.concatenate([y1, y2]),
                                  np.asarray(whole_y))


def test_the_models_scan_is_the_kernel_and_the_reference_stands_beside():
    """One path: ``hybrid_model.ssm_scan`` hands off to the kernel, both
    models call it by that name, and nothing of ``serving/`` names the XLA
    form."""
    import os

    assert jamba_model.ssm_scan is hybrid_model.ssm_scan
    args = _inputs(128, 128)
    got = hybrid_model.ssm_scan(*args)
    want = pallas_ops.ssm_scan(*args)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    serving = os.path.dirname(hybrid_model.__file__)
    for name in os.listdir(serving):
        if name.endswith(".py"):
            with open(os.path.join(serving, name)) as f:
                assert "ssm_scan_reference" not in f.read(), name


def test_jamba_chunk_program_calls_the_module_level_ssm_scan(monkeypatch):
    """The seam ``benchmark/tests/broken_jamba.py`` plants its
    ``scan_from_zero`` fault through: a wrapper of the same signature put
    in ``jamba_model.ssm_scan`` is what a chunk program traces, once a run
    of Mamba layers, with the slot's state as ``s0``."""
    from brpc_tpu.serving import HybridCacheConfig, JambaConfig, JambaModel

    assert list(inspect.signature(jamba_model.ssm_scan).parameters) == \
        ["dt", "u", "bm", "cm", "a", "s0"]
    assert inspect.signature(jamba_model.ssm_scan).parameters[
        "s0"].default is None
    calls = []
    orig = jamba_model.ssm_scan

    def recorder(dt, u, bm, cm, a, s0=None):
        calls.append((dt.shape, u.shape, bm.shape, cm.shape, a.shape,
                      None if s0 is None else s0.shape))
        return orig(dt, u, bm, cm, a, s0)

    monkeypatch.setattr(jamba_model, "ssm_scan", recorder)
    cfg = JambaConfig(hidden_size=64, num_attention_heads=4,
                      num_hidden_layers=4, attn_layer_period=4,
                      attn_layer_offset=1, max_context=512)
    kv = cfg.cache(HybridCacheConfig(block_size=16, num_blocks=32,
                                     max_sequences=2))
    model = JambaModel(cfg, kv)
    prompt = model.synth_prompt(200)
    table = kv.alloc_sequence(1, len(prompt))
    model.prefill_suffix(prompt[:128], table, 0)
    first = len(calls)
    model.prefill_suffix(prompt, table, 128)
    # layers 0 | 2, 3 are Mamba: two runs, each traced once a program
    assert first == 2 and len(calls) == 4
    di = cfg.d_inner
    assert calls[0] == ((128, di), (128, di), (128, N), (128, N), (N, di),
                        (N, di))
    assert model.scan_counters == {"launches": 2, "rows": 2 * 128 * 3}
    kv.free_sequence(1)
    model.close()
