"""Mesh management + tpu:// device naming.

The TPU build's "cluster view": where the reference enumerates ip:port
servers through naming services (SURVEY §2.4 naming row), we enumerate the
device mesh. A ``tpu://`` URL names one chip; ``tpu://mesh/<axis>`` names a
whole mesh axis as a collective target (ParallelChannel/PartitionChannel
lower onto these, SURVEY §2.5 table).

Standard axis vocabulary (the scaling-book recipe: pick a mesh, annotate,
let XLA insert collectives):
  dp — data parallel (batch)       tp — tensor parallel (model width)
  sp — sequence parallel (context) pp — pipeline stages
  ep — expert parallel (MoE)
"""

from __future__ import annotations

import threading
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from brpc_tpu.butil.endpoint import EndPoint

_lock = threading.Lock()
_default_mesh = None


def devices():
    import jax

    return jax.devices()


def device_count() -> int:
    return len(devices())


def describe_devices() -> str:
    """``platform=... device_kind=... count=...`` as JAX reports it — the
    tag every bench and smoke line carries to name the device it ran on."""
    devs = devices()
    return (f"platform={devs[0].platform} "
            f"device_kind={devs[0].device_kind!r} count={len(devs)}")


def list_device_endpoints(host: str = "localhost") -> List[EndPoint]:
    """The tpu:// naming view of the local process (one EndPoint per chip)."""
    return [
        EndPoint.from_tpu(host, d.id) for d in devices()
    ]


def resolve_device(ep: EndPoint):
    """tpu://host/ordinal -> jax Device."""
    if not ep.is_tpu():
        raise ValueError(f"not a tpu endpoint: {ep}")
    for d in devices():
        if d.id == ep.device_ordinal:
            return d
    raise ValueError(f"no local device with ordinal {ep.device_ordinal}")


def make_mesh(axis_sizes: Dict[str, int], devices_list=None):
    """Build a jax.sharding.Mesh with named axes.

    axis_sizes: ordered {axis_name: size}; sizes must multiply to the
    device count (a -1 size is inferred).
    """
    import jax
    from jax.sharding import Mesh

    devs = list(devices_list if devices_list is not None else jax.devices())
    names = list(axis_sizes.keys())
    sizes = list(axis_sizes.values())
    if -1 in sizes:
        known = int(np.prod([s for s in sizes if s != -1]))
        sizes[sizes.index(-1)] = len(devs) // known
    total = int(np.prod(sizes))
    if total != len(devs):
        raise ValueError(
            f"mesh {dict(zip(names, sizes))} needs {total} devices, "
            f"have {len(devs)}"
        )
    arr = np.array(devs).reshape(sizes)
    return Mesh(arr, axis_names=tuple(names))


def mesh_factors(n: int) -> Tuple[int, int, int]:
    """Split n devices into (dp, sp, tp), preferring to use every axis —
    the same split the multichip dryrun proves (8 -> dp=2 sp=2 tp=2).
    Any n works: odd counts fold the even axes to 1."""
    tp = 2 if n % 2 == 0 else 1
    rem = n // tp
    sp = 2 if rem % 2 == 0 else 1
    dp = rem // sp
    return dp, sp, tp


def serving_mesh(devices_list=None):
    """The serving plane's dp/sp/tp mesh over the local devices: dp shards
    the request batch (and the KV pools), sp carries the ring-attention
    long-context lane, tp shards attention heads in prefill. Degenerates
    to a 1x1x1 mesh on a single chip, so the sharded serving stack is the
    only stack — there is no separate single-device code path to drift."""
    import jax

    devs = list(devices_list if devices_list is not None
                else jax.devices())
    dp, sp, tp = mesh_factors(len(devs))
    return make_mesh({"dp": dp, "sp": sp, "tp": tp}, devices_list=devs)


def default_mesh(axis_name: str = "x"):
    """Process-wide 1-D mesh over all devices (the 'whole ring')."""
    global _default_mesh
    with _lock:
        if _default_mesh is None or _default_mesh.axis_names != (axis_name,):
            _default_mesh = make_mesh({axis_name: -1})
        return _default_mesh


def named_sharding(mesh, *spec):
    from jax.sharding import NamedSharding, PartitionSpec

    return NamedSharding(mesh, PartitionSpec(*spec))
