"""Sharded dispatch plane — the Python lane spread over N worker processes.

PR 9 (docs/small-message-fastpath.md) measured the single-core ceiling:
~323µs of irreducible Python CPU per call, all latency tricks applied. The
reference escapes this with bthread's M:N scheduler spreading work over
every core (PAPER.md, runtime layer); CPython cannot — one GIL per
process — and same-process subinterpreter dispatch was a recorded
negative result (docs/round5-notes.md). So our idiomatic analog is OS
processes: a parent keeps owning the tunnel's control plane (handshake,
epochs, credit window, healer) while the CPU-heavy middle — TRPC frame
parse, method dispatch, response pack — runs in worker processes.

The contract that makes this cheap is **handles cross the process
boundary, never bytes that own anything**:

- workers map the SAME shm block pools the tunnel already registered,
  *by name* (the pool name went over the HELLO wire for exactly this
  reason) — a bulk response is memcpy'd once, by the worker, directly
  into client-visible registered memory;
- the parent leases each worker a **credit sub-window** — block indices
  acquired from its PeerWindow — so workers never talk to the credit
  machinery, and a dead worker's lease is reclaimed wholesale;
- requests/responses cross on shm SPSC byte rings as raw wire frames +
  integer handles (see wire.py); the ``cross-process-ownership`` tpulint
  rule enforces that no ``IOBuf``/``Block``/socket object is ever
  pickled across.

Responses fan back in through the parent's existing coalesced-doorbell
write (``TpuEndpoint.fan_in_flush``): one collector thread drains every
worker's ring and posts a poll batch of worker responses as ONE ctrl
write. Worker death rides the healer philosophy: a ``worker.crash``
fault point for chaos tests, parent-side respawn with a generation
bump, and every in-flight cid on the dead worker fanned a retriable
code exactly like tunnel death does.

``tpu_shard_workers=0`` (the default) is a strict no-op: no process is
spawned, no lane hook installed, the PR-9 fastpath runs unchanged.
"""

from __future__ import annotations

from brpc_tpu import fault as _fault

# chaos hook: SIGKILL worker <match_worker> (or any worker when unmatched)
# from the plane's monitor loop — the shard analog of tpu.tunnel.kill.
# Needs the fault_injection_enabled master gate like every fault point.
_fault.register(
    "worker.crash",
    "SIGKILL a shard dispatch worker from the plane monitor "
    "(match_worker=<index> targets one); exercises lease reclaim, "
    "retriable fan-out to in-flight cids, and generation-bump respawn")
