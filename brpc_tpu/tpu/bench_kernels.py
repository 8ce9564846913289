"""Benchmark kernels: the device-resident echo datapath.

The TpuSocket steady state keeps payloads on-device (the design goal:
minimize host<->HBM crossings, SURVEY §5.8). One "echo" = payload DMA'd from
the client-side buffer to the server-side buffer and back — two full HBM
passes. Expressed as a pallas copy kernel (VMEM-staged, grid over blocks) so
XLA cannot fuse or elide the movement; payloads are sized past VMEM so the
traffic is genuinely HBM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


BLOCK = 1 << 20  # 1MB VMEM staging blocks


def _copy_kernel(src_ref, dst_ref):
    dst_ref[:] = src_ref[:]


@functools.partial(jax.jit, static_argnames=("interpret",))
def hbm_copy(x, interpret: bool = False):
    """HBM -> HBM copy staged through VMEM blocks (one full read+write)."""
    from jax.experimental import pallas as pl

    n = x.shape[0]
    block = min(BLOCK, n)
    grid = (n // block,)
    return pl.pallas_call(
        _copy_kernel,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        grid=grid,
        in_specs=[pl.BlockSpec((block,), lambda i: (i,))],
        out_specs=pl.BlockSpec((block,), lambda i: (i,)),
        interpret=interpret,
    )(x)


@functools.partial(jax.jit, static_argnames=("rounds", "interpret"))
def echo_loop(x, rounds: int = 8, interpret: bool = False):
    """`rounds` echo round-trips: client buf -> server buf -> client buf.

    Returns the final client buffer (bit-identical to x) so correctness is
    checkable. 4 full HBM passes per round (2 copies x read+write).
    """

    def body(i, buf):
        server_side = hbm_copy2d(buf, interpret=interpret)
        client_side = hbm_copy2d(server_side, interpret=interpret)
        return client_side

    return jax.lax.fori_loop(0, rounds, body, x)


ROW_BLOCK = 512


@functools.partial(jax.jit, static_argnames=("interpret",))
def hbm_copy2d(x, interpret: bool = False):
    """HBM -> HBM copy of a [rows, lanes] array, VMEM-staged row blocks."""
    from jax.experimental import pallas as pl

    rows, lanes = x.shape
    block = min(ROW_BLOCK, rows)
    return pl.pallas_call(
        _copy_kernel,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        grid=(rows // block,),
        in_specs=[pl.BlockSpec((block, lanes), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((block, lanes), lambda i: (i, 0)),
        interpret=interpret,
    )(x)


@functools.partial(jax.jit, static_argnames=("rounds", "interpret"))
def echo_loop_probe(x, rounds: int, interpret: bool = False):
    """echo_loop + a dependent scalar (first+last element): a 4-byte fetch
    both completes the loop and checks that the copies preserved the
    data."""
    if x.ndim != 2:
        raise ValueError("probe expects a 2-D payload")
    out = jax.lax.fori_loop(
        0, rounds,
        lambda i, b: hbm_copy2d(hbm_copy2d(b, interpret=interpret),
                                interpret=interpret),
        x,
    )
    return out[0, 0] + out[-1, -1]
