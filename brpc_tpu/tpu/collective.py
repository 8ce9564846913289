"""Collective lowering — combo-channel semantics on mesh axes.

This is where the reference's fan-out vocabulary (SURVEY §2.5) becomes XLA
collectives over ICI:

  ParallelChannel  (same req -> N replicas, merge responses)
      -> fanout(): shard_map over an axis + psum/all_gather merge
  PartitionChannel (req -> partition p of N)
      -> partition(): shard_map with partitioned inputs, no merge
  Streaming pipelining
      -> ring neighbor exchange (ppermute), see ring.py

XLA's built-in psum/all_gather lower to the platform-optimal ICI algorithm;
the explicit ring_* variants express the same math as neighbor exchanges —
they are the building block for overlap patterns (ring attention) and for
validating collective numerics hop by hop.
"""

from __future__ import annotations

from functools import partial
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P


def shard_map_norep(f, mesh: Mesh, in_specs, out_specs):
    """shard_map with the varying-axes checker OFF — for bodies that
    write their collectives by hand (manual psum/all_gather,
    interpreted-Pallas kernels the checker rejects)."""
    return shard_map(f, mesh=mesh, in_specs=in_specs,
                     out_specs=out_specs, check_vma=False)


# ------------------------------------------------------------------ wrappers
def all_reduce(x, mesh: Mesh, axis: str):
    """Sum across the axis; every shard gets the total (ParallelChannel with
    a summing ResponseMerger)."""

    @partial(shard_map, mesh=mesh, in_specs=P(axis), out_specs=P(axis))
    def _f(shard):
        return lax.psum(shard, axis)

    return _f(x)


def all_gather(x, mesh: Mesh, axis: str):
    """Every shard receives the concatenation along the sharded dim."""

    @partial(shard_map, mesh=mesh, in_specs=P(axis), out_specs=P(axis))
    def _f(shard):
        return lax.all_gather(shard, axis, tiled=True)

    return _f(x)


def reduce_scatter(x, mesh: Mesh, axis: str):
    """x: [n, m] sharded on dim0 (each device contributes one row). Result:
    the row-sum [m], distributed so device i owns slice i — returned as the
    assembled [m] global array."""

    @partial(shard_map, mesh=mesh, in_specs=P(axis, None), out_specs=P(axis))
    def _f(shard):
        return lax.psum_scatter(shard[0], axis, scatter_dimension=0,
                                tiled=True)

    return _f(x)


def all_to_all(x, mesh: Mesh, axis: str, split_axis: int, concat_axis: int):
    """Transpose shard ownership (the Ulysses-style sequence<->head swap)."""

    @partial(shard_map, mesh=mesh, in_specs=P(axis), out_specs=P(axis))
    def _f(shard):
        return lax.all_to_all(shard, axis, split_axis=split_axis,
                              concat_axis=concat_axis, tiled=True)

    return _f(x)


def shift(x, mesh: Mesh, axis: str, offset: int = 1):
    """Rotate shards around the ring (ppermute) — the neighbor exchange."""

    n = mesh.shape[axis]
    perm = [(i, (i + offset) % n) for i in range(n)]

    @partial(shard_map, mesh=mesh, in_specs=P(axis), out_specs=P(axis))
    def _f(shard):
        return lax.ppermute(shard, axis, perm)

    return _f(x)


# ---------------------------------------------------------- explicit rings
def ring_all_reduce(x, mesh: Mesh, axis: str):
    """Bandwidth-optimal ring allreduce expressed as 2(n-1) neighbor hops
    (reduce-scatter phase then all-gather phase). x: [n, m] with row i the
    local array of device i (m divisible by n); every row of the result is
    the row-sum. Numerically matches psum; exists to (a) validate hop-level
    numerics, (b) serve as the scheduling skeleton for overlapped variants."""

    n = mesh.shape[axis]
    fwd = [(i, (i + 1) % n) for i in range(n)]

    @partial(shard_map, mesh=mesh, in_specs=P(axis, None),
             out_specs=P(axis, None))
    def _f(shard):
        local = shard[0]  # this device's full local array [m]
        if n == 1:
            return local[None]
        my = lax.axis_index(axis)
        chunks = jnp.stack(jnp.split(local, n, axis=0))  # [n, m/n]

        # phase 1: reduce-scatter. After n-1 hops, chunk (my+1) holds the
        # full sum on this device.
        def rs_step(i, chunks):
            # each device sends the chunk it just accumulated to its right
            # neighbor; chunk index walks backwards from my
            send_idx = (my - i) % n
            block = lax.dynamic_index_in_dim(chunks, send_idx, axis=0,
                                             keepdims=False)
            recvd = lax.ppermute(block, axis, fwd)
            recv_idx = (my - i - 1) % n
            old = lax.dynamic_index_in_dim(chunks, recv_idx, axis=0,
                                           keepdims=False)
            return lax.dynamic_update_index_in_dim(
                chunks, old + recvd, recv_idx, axis=0
            )

        chunks = lax.fori_loop(0, n - 1, rs_step, chunks)

        # phase 2: all-gather the reduced chunks around the ring
        def ag_step(i, chunks):
            send_idx = (my - i + 1) % n
            block = lax.dynamic_index_in_dim(chunks, send_idx, axis=0,
                                             keepdims=False)
            recvd = lax.ppermute(block, axis, fwd)
            recv_idx = (my - i) % n
            return lax.dynamic_update_index_in_dim(
                chunks, recvd, recv_idx, axis=0
            )

        chunks = lax.fori_loop(0, n - 1, ag_step, chunks)
        return jnp.concatenate(list(chunks), axis=0)[None]

    return _f(x)


# ----------------------------------------------------- combo-channel shapes
def fanout(fn: Callable, mesh: Mesh, axis: str, merge: str = "gather"):
    """ParallelChannel: run fn on every shard, merge results.

    merge: 'gather' (concat sub-responses — the CallMapper/default merger),
           'sum' (psum — an aggregating ResponseMerger),
           'none' (leave sharded — caller merges).
    """

    def wrapped(x):
        @partial(shard_map, mesh=mesh, in_specs=P(axis), out_specs=P(axis))
        def _f(shard):
            out = fn(shard)
            if merge == "sum":
                return lax.psum(out, axis)
            if merge == "gather":
                return lax.all_gather(out, axis, tiled=True)
            return out

        return _f(x)

    return wrapped


def partition(fn: Callable, mesh: Mesh, axis: str):
    """PartitionChannel: each partition handles its shard; results stay
    partitioned (partition_channel.h:46-136 semantics on an axis)."""

    def wrapped(x):
        @partial(shard_map, mesh=mesh, in_specs=P(axis), out_specs=P(axis))
        def _f(shard):
            return fn(shard)

        return _f(x)

    return wrapped


# ------------------------------------------------ ParallelChannel lowering
# The registry + entry point the RPC layer's CollectiveScheme drives
# (rpc/combo_channels.py): fn must be known BY NAME on both execution paths
# (the shard_map program here, the device-method RPC fallback there).
_collective_fns = {}


def register_collective_fn(name: str, fn: Callable) -> None:
    _collective_fns[name] = fn


def collective_fn(name: str) -> Callable:
    fn = _collective_fns.get(name)
    if fn is None:
        raise KeyError(f"no collective fn registered as {name!r}")
    return fn


def fanout_call(fn: Callable, mesh: Mesh, axis: str, merge: str, x):
    """ParallelChannel fan-out as ONE program: x shards over `axis` (dim
    0), fn runs per shard, the MERGER is the collective. Result semantics
    match the RPC fallback exactly:

      gather -> concat of per-shard responses in sub-channel order
                (the default MergeFrom/repeated-field concatenation)
      sum    -> ONE summed response (an aggregating ResponseMerger)
      none   -> concat, same as gather (results stay per-partition)
    """
    if merge == "sum":
        @partial(shard_map, mesh=mesh, in_specs=P(axis), out_specs=P())
        def _sum(shard):
            return lax.psum(fn(shard), axis)

        return _sum(x)

    @partial(shard_map, mesh=mesh, in_specs=P(axis), out_specs=P(axis))
    def _gather(shard):
        return fn(shard)

    return _gather(x)
