"""Iteration-level scheduler: continuous batching over the paged KV cache.

Orca-style scheduling mapped onto this repo's server: the engine thread
runs a step loop where each step is a mixed prefill+decode batch under a
token budget, and new requests are admitted *between* decode steps —
a long generation never blocks a short one behind it (continuous
batching).

Admission is where policy concentrates, mirroring the server's own
front door:

- **deadline** — a request whose client budget is already spent
  (``cntl.deadline_mono``, stamped by server-side deadline enforcement)
  is rejected with ERPCTIMEDOUT before it ever holds KV blocks; the same
  re-check the batch runtime does at enqueue.
- **KV watermark** — :meth:`PagedKVCache.can_admit` keeps decode headroom
  above the watermark; rejects surface EOVERCROWDED, which the tunnel
  retry policy already backs off on.
- **queue depth** — a bounded waiting queue, EOVERCROWDED past the cap.

Each step issues ONE fused device program for the whole decode batch and
one per prefill (see serving/model.py) — dispatch coalescing at the step
level. A model that can go on from what an earlier launch left of a prompt
(``CONTINUES_PREFILL``: serving/hybrid_model.py) has a prompt longer than
the step's budget prefilled a CHUNK a step: the sequence holds its slot and
pages, stays out of the decode batch until its last chunk yields the first
token, and every step still runs the decode launch — a live sequence waits
one chunk, not one prompt. Tokens are host-materialized exactly once per
step; per-token streaming writes fan out of that single sync (tpulint's
``no-per-token-host-sync`` rule keeps it that way).

Streaming: a request that arrived with stream settings gets TokenDelta
frames as steps complete, so TTFT is a stream-arrival time, decoupled
from the RPC response (which carries the full token list at completion).

Fault points: ``serving.decode.stall`` (injects latency into the step
loop) and ``serving.kv.exhaust`` (forces admission rejections). A tunnel
kill mid-generation is detected via the request socket's failed flag;
in-flight sequences are aborted with EFAILEDSOCKET (retriable) and every
KV block returns to the pool — ``assert_idle`` audits that, the way the
CreditLedger audits window teardown.
"""

from __future__ import annotations

import collections
import math
import threading
import time
from typing import Deque, Dict, List, Optional

import numpy as np

from brpc_tpu import fault as _fault
from brpc_tpu.metrics.latency_recorder import LatencyRecorder
from brpc_tpu.metrics.reducer import Adder
from brpc_tpu.metrics.status import PassiveStatus
from brpc_tpu.profiling import registry as _prof
from brpc_tpu.profiling.registry import span as _span
from brpc_tpu.profiling.registry import wait_span as _wait_span
from brpc_tpu.rpc import errors
from brpc_tpu.rpc import native_transport as _native_transport
from brpc_tpu.serving import qos as _qos
from brpc_tpu.serving import speculative as _spec
from brpc_tpu.serving.kv_cache import KVCacheFull, PagedKVCache
from brpc_tpu.serving.model import TinyTransformer

_fault.register("serving.decode.stall",
                "stall the serving engine's decode step (delay_ms=)")
_fault.register("serving.kv.exhaust",
                "force KV-pool admission rejections (EOVERCROWDED)")

g_serving_steps = Adder("g_serving_steps")
g_serving_tokens = Adder("g_serving_tokens")
g_serving_prefill_tokens = Adder("g_serving_prefill_tokens")
g_serving_admitted = Adder("g_serving_admitted")
g_serving_rejected = Adder("g_serving_rejected")
g_serving_aborted = Adder("g_serving_aborted")
g_serving_completed = Adder("g_serving_completed")
g_serving_deadline_rejects = Adder("g_serving_deadline_rejects")
g_serving_step = LatencyRecorder().expose("g_serving_step")
g_serving_ttft = LatencyRecorder().expose("g_serving_ttft")
g_serving_itl = LatencyRecorder().expose("g_serving_itl")

_engines: List["ServingEngine"] = []
_engines_lock = threading.Lock()


def _ns_to_us(ns: int) -> float:
    return round(ns / 1000.0, 1)


def active_engines() -> List["ServingEngine"]:
    with _engines_lock:
        return [e for e in _engines if e.running]


def _sum_engines(fn) -> int:
    return sum(fn(e) for e in active_engines())


g_serving_queue_depth = PassiveStatus(
    lambda: _sum_engines(lambda e: e.queue_depth)) \
    .expose("g_serving_queue_depth")
g_serving_queue_depth.prometheus_type = "gauge"
g_serving_running = PassiveStatus(
    lambda: _sum_engines(lambda e: e.running_count)) \
    .expose("g_serving_running")
g_serving_running.prometheus_type = "gauge"


ROLE_PREFILL = "prefill"
ROLE_DECODE = "decode"
ROLE_BOTH = "both"


class EngineConfig:
    def __init__(self, max_batch: int = 8, token_budget: int = 512,
                 max_queue: int = 64, max_new_tokens_cap: int = 512,
                 idle_wait_s: float = 0.05, role: str = ROLE_BOTH,
                 spec_k: int = 0, spec_ngram: int = 3,
                 spec_collapse_after: int = 4, qos=None):
        if role not in (ROLE_PREFILL, ROLE_DECODE, ROLE_BOTH):
            raise ValueError(f"unknown role {role!r}")
        if spec_k < 0:
            raise ValueError(f"spec_k must be >= 0, got {spec_k}")
        self.max_batch = max_batch
        # per-step budget over prefill tokens + one decode token per
        # running sequence — the Orca iteration-level knob. It gates
        # admission; for a model that can continue a prefill it also cuts
        # a longer prompt into chunks of what the budget leaves a step
        self.token_budget = token_budget
        self.max_queue = max_queue
        self.max_new_tokens_cap = max_new_tokens_cap
        self.idle_wait_s = idle_wait_s
        # disaggregated serving: a "prefill" engine runs prefill then
        # migrates each chain to its KVMigrator's destination (falling
        # back to local decode when migration fails); a "decode" engine
        # mostly adopts migrated sequences but still accepts fresh
        # submissions (roles are scheduling placement, not capability)
        self.role = role
        # speculative decoding: spec_k > 0 turns each decode step into
        # draft-k + one fused verify (serving/speculative.py); per
        # sequence the AdaptiveK controller shrinks k on rejection and
        # collapses to plain decode after spec_collapse_after
        # consecutive zero-accept steps
        self.spec_k = spec_k
        self.spec_ngram = spec_ngram
        self.spec_collapse_after = spec_collapse_after
        # multi-tenant QoS: a serving.qos.QosConfig turns admission into
        # weighted fair share + the closed-loop overload governor; None
        # keeps the single-tenant FIFO path byte-for-byte as before
        self.qos = qos


STATE_WAITING = "waiting"
STATE_RUNNING = "running"
STATE_DONE = "done"


class Sequence:
    """One in-flight generation request."""

    _ids = [0]
    _ids_lock = threading.Lock()

    def __init__(self, prompt: np.ndarray, max_new_tokens: int,
                 stop_token: int = 0, cntl=None, done=None,
                 stream_id: int = 0, tenant_id: str = "",
                 priority: int = 0):
        with Sequence._ids_lock:
            Sequence._ids[0] += 1
            self.seq_id = Sequence._ids[0]
        self.prompt = prompt
        self.max_new_tokens = max_new_tokens
        self.stop_token = stop_token
        self.cntl = cntl
        self.done = done
        self.stream_id = stream_id
        # QoS identity (decoded off RequestMeta by the dispatch paths):
        # which fair-share lane this bills, how protected under shedding
        self.tenant_id = tenant_id
        self.priority = priority
        self.state = STATE_WAITING
        self.out_tokens: List[int] = []
        # tokens covered by a forked prefix-cache chain (block-aligned);
        # prefill runs only the suffix past this point
        self.prefix_len = 0
        # rows of the prompt prefilled so far (chunked prefill: under
        # len(prompt) while the sequence is mid-prompt)
        self.prefilled = 0
        self.t_submit = time.monotonic()
        self.t_admit = 0.0   # when admission moved it to running
        self.t_first_token = 0.0
        self.t_last_token = 0.0
        self.finish_reason = ""
        # disaggregation: a migrated-in sequence is "adopted" and decodes
        # with no client bound until a stage-2/retry Generate attaches;
        # handoff_base marks how many out_tokens the prefill shard
        # already returned (a resume attach replies only the suffix)
        self.adopted = False
        self.handoff_base = 0
        self.resume_attach = False
        self._attached = False
        self._deferred: Optional[tuple] = None
        self.t_adopted = 0.0
        # speculative decoding: per-sequence adaptive draft-length
        # controller, created lazily by the engine when spec_k > 0
        self.spec = None

    @property
    def pos(self) -> int:
        """0-based position of the NEXT token to append."""
        return len(self.prompt) + len(self.out_tokens) - 1

    def context_len(self) -> int:
        return len(self.prompt) + len(self.out_tokens)


class ServingEngine:
    def __init__(self, model: TinyTransformer, kv: Optional[PagedKVCache] = None,
                 config: Optional[EngineConfig] = None, prefix_cache=None):
        self.model = model
        self.kv = kv if kv is not None else model.kv
        self.config = config or EngineConfig()
        if getattr(self.kv, "state_overwritten", False):
            # rows or a state overwritten in place (window rings, recurrent
            # state) cannot be rolled back past a rejected draft, and
            # nothing moves them between engines yet
            if self.config.spec_k > 0:
                raise ValueError(
                    "speculative decoding (spec_k > 0) over a cache manager "
                    "that overwrites state in place (window rings, "
                    "recurrent state) is not supported")
            if self.config.role != ROLE_BOTH:
                raise ValueError(
                    f"role {self.config.role!r} migrates sequences, which a "
                    f"cache manager that overwrites state in place (window "
                    f"rings, recurrent state) does not support")
        # radix prefix cache: None auto-builds over the pool (gated per
        # admission by the serving_prefix_cache_enabled flag), False
        # disables outright (cold A/B lanes, oracle reference engines)
        if prefix_cache is None and hasattr(model, "prefill_suffix"):
            from brpc_tpu.serving.prefix_cache import build_prefix_cache
            prefix_cache = build_prefix_cache(self.kv)
        self.prefix = prefix_cache or None
        self._cv = threading.Condition()
        self._waiting: Deque[Sequence] = collections.deque()
        self._running: List[Sequence] = []
        # chunked prefill: the ONE admitted sequence whose prompt is still
        # being prefilled (slot and pages held, not in the decode batch);
        # rows a chunk is a multiple of (the model's scan chunk and the
        # cache's block), 0 for a model that cannot continue a prefill
        self._prefilling: Optional[Sequence] = None
        self._chunk_unit = 0
        if getattr(model, "CONTINUES_PREFILL", False):
            self._chunk_unit = math.lcm(int(model.PREFILL_GRANULE),
                                        int(self.kv.block_size))
        self.prefill_chunks = 0       # launches that were part of a prompt
        self.prefill_chunk_rows = 0   # rows those launches prefilled
        self._thread: Optional[threading.Thread] = None
        self.running = False
        self.steps = 0
        self.tokens_generated = 0
        self.last_step_us = 0.0
        self._occupancy_sum = 0
        # time between submit and admission, over the sequences admitted
        self.admitted = 0
        self.queue_wait_us_sum = 0.0
        # the loop thread's span counters (profiling/registry.py), kept
        # here so snapshot() reads them from any thread
        self._spans: Dict[str, List[int]] = {}
        self._waits: Dict[str, int] = {}     # its waiting spans' CPU time
        # disaggregation plumbing: the migrator ships chains OUT (set via
        # set_migrator), the receiver (installed by LlmServingService)
        # adopts chains IN; _adopted parks migrated-in sequences until a
        # stage-2/retry Generate attaches a client to them
        self.migrator = None
        self._migration_rx = None
        self._adopted: Dict[int, Sequence] = {}
        # adopted chains wait here for a max_batch slot — direct entry
        # into _running would let migration bursts inflate the decode
        # batch past any size admission ever dispatches
        self._adopted_pending: Deque[Sequence] = collections.deque()
        # serializes pool mutation between the step loop (prefill/decode
        # donate the pool buffers) and migration adoption's host-side
        # scatter — concurrent writers see deleted/donated buffers
        self.pool_gate = threading.Lock()
        self._recover_index: Dict[tuple, Deque[int]] = {}
        # per-engine counter the disaggregation oracle needs (the
        # g_serving_* fleet vars cannot isolate one engine)
        self.prefill_tokens = 0
        # speculative decoding: per-engine counters (the oracle needs
        # per-lane isolation, like the field above)
        self.spec_stats = (_spec.SpecStats()
                           if self.config.spec_k > 0 else None)
        # multi-tenant QoS: the fair-share scheduler replaces _waiting
        # as the queue substrate and the governor closes the overload
        # loop from the sampler tick (installed in start())
        self.qos = (_qos.TenantScheduler(self.config.qos, engine=self)
                    if self.config.qos is not None else None)
        self._qos_governor = (_qos.QosGovernor(self)
                              if self.qos is not None else None)
        # per-shard decode attribution: shard -> [steps, total_us,
        # last_us, seq_steps] (only shards with live sequences tick)
        self._shard_step: Dict[int, List[float]] = {}
        with _engines_lock:
            _engines.append(self)

    # ------------------------------------------------------------ lifecycle
    def start(self) -> "ServingEngine":
        with self._cv:
            if self.running:
                return self
            self.running = True
        if self._qos_governor is not None:
            # close the loop: the governor rides the 1 Hz sampler tick,
            # sampling the queue-wait series ring the sweep just filled
            from brpc_tpu.metrics.series import (ensure_series_installed,
                                                 global_series)

            ensure_series_installed()
            hooks = global_series().post_tick_hooks
            if self._qos_governor not in hooks:
                hooks.append(self._qos_governor)
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name="brpc-serving-engine")
        self._thread.start()
        return self

    def set_migrator(self, migrator) -> "ServingEngine":
        """Install the outbound KV migrator (serving/migration.py). A
        prefill-role engine hands every prefilled chain to it from the
        step loop; ANY engine with one drains live sequences to the
        destination on stop() instead of aborting them from scratch."""
        if getattr(self.kv, "state_overwritten", False):
            raise ValueError("migration over a cache manager that overwrites "
                             "state in place (window rings, recurrent state) "
                             "is not supported")
        self.migrator = migrator
        return self

    def stop(self, abort_code: int = errors.ELOGOFF) -> None:
        with self._cv:
            if not self.running:
                return
            self.running = False
            self._cv.notify_all()
        if self._qos_governor is not None:
            from brpc_tpu.metrics.series import global_series

            try:
                global_series().post_tick_hooks.remove(self._qos_governor)
            except ValueError:
                pass
        if self._thread is not None:
            self._thread.join(timeout=10.0)
            self._thread = None
        # shard-death recovery: with a migrator installed, live chains
        # move to the survivor (the step loop is parked, so every
        # sequence is quiescent) instead of dying retry-from-scratch
        if self.migrator is not None:
            self._drain_migrate()
        # fan a retriable error to anything still in flight, then prove
        # the pool whole — the CreditLedger teardown discipline
        self._abort_all_locked_out(abort_code, "engine stopped")
        with self._cv:
            self._adopted.clear()
            self._recover_index.clear()
        if self.prefix is not None:
            # release every tree hold so assert_idle sees the pool whole
            self.prefix.clear()
        with _engines_lock:
            if self in _engines:
                _engines.remove(self)

    # ------------------------------------------------------------ admission
    def submit(self, prompt: np.ndarray, max_new_tokens: int,
               stop_token: int = 0, cntl=None, done=None,
               stream_id: int = 0,
               resume_seq_id: int = 0, tenant_id: Optional[str] = None,
               priority: Optional[int] = None,
               _synthetic: bool = False) -> "tuple[int, Optional[Sequence]]":
        """Admission front door (runs on the RPC thread). Returns
        (error_code, seq): 0 + the queued sequence, or a reject code the
        caller surfaces through cntl.set_failed.

        ``resume_seq_id`` attaches to a migrated-in sequence (two-stage
        disaggregated dispatch: the stage-1 handoff reply named it) —
        no admission, no allocation, the chain is already here.

        ``tenant_id``/``priority`` default to the wire identity on
        ``cntl`` (RequestMeta → dispatch → cntl); pass them explicitly
        when no controller carries them. ``_synthetic`` marks burst
        clones fabricated by the serving.qos.burst fault point so they
        cannot re-trigger it."""
        # the link between a request's RPC spans (correlation id) and its
        # engine spans (seq): this span lies inside the caller's rpc.execute
        with _span("engine.submit") as sp:
            code, seq = self._submit(
                prompt, max_new_tokens, stop_token, cntl, done, stream_id,
                resume_seq_id, tenant_id, priority, _synthetic)
            if seq is not None:
                meta = getattr(cntl, "_srv_meta", None)
                sp.note(seq=seq.seq_id,
                        cid=meta.correlation_id if meta is not None else 0)
        return code, seq

    def _submit(self, prompt, max_new_tokens, stop_token, cntl, done,
                stream_id, resume_seq_id, tenant_id, priority, _synthetic):
        if resume_seq_id:
            with self._cv:
                seq = self._adopted.get(resume_seq_id)
            if seq is None:
                return errors.EREQUEST, None
            return self._bind_attach(seq, cntl, done, stream_id,
                                     resume=True)
        if max_new_tokens < 1:
            return errors.EREQUEST, None
        max_new_tokens = min(max_new_tokens, self.config.max_new_tokens_cap)
        if len(prompt) < 1 or (len(prompt) + max_new_tokens
                               > self.model.config.max_context):
            return errors.EREQUEST, None
        # shard-death recovery: a retried request whose sequence was
        # drain-migrated here picks up the live generation instead of
        # re-prefilling a single token
        if self._recover_index:
            cand = self._recover_match(prompt, max_new_tokens, stop_token)
            if cand is not None:
                return self._bind_attach(cand, cntl, done, stream_id,
                                         resume=False)
        # deadline at admission (PR 4's server-side enforcement, re-checked
        # here exactly like the batch runtime re-checks at enqueue)
        deadline = getattr(cntl, "deadline_mono", 0.0) if cntl else 0.0
        if deadline and time.monotonic() >= deadline:
            g_serving_deadline_rejects.put(1)
            g_serving_rejected.put(1)
            return errors.ERPCTIMEDOUT, None
        if _fault.hit("serving.kv.exhaust") is not None:
            self.kv.note_rejected()
            g_serving_rejected.put(1)
            return errors.EOVERCROWDED, None
        if tenant_id is None:
            tenant_id = getattr(cntl, "tenant_id", "") if cntl else ""
        if priority is None:
            priority = getattr(cntl, "priority", 0) if cntl else 0
        if self.qos is not None and not _synthetic:
            # chaos: inflate this tenant's arrival rate at admission —
            # each real submit fans out factor-1 synthetic clones that
            # bill the same lane (and shed the same way)
            burst = _fault.hit("serving.qos.burst", tenant=tenant_id)
            if burst is not None:
                for _ in range(max(0, int(burst.get("factor", 2)) - 1)):
                    self.submit(prompt, max_new_tokens,
                                stop_token=stop_token,
                                tenant_id=tenant_id, priority=priority,
                                _synthetic=True)
        with self._cv:
            if not self.running:
                return errors.ELOGOFF, None
            if self.qos is None \
                    and len(self._waiting) >= self.config.max_queue:
                g_serving_rejected.put(1)
                return errors.EOVERCROWDED, None
            # watermark backpressure counts queued-but-unadmitted prefill
            # tokens too, else a burst overcommits the pool before the
            # step loop catches up. The sequence exists before the check
            # so a sharded pool can route it (route_key -> owning shard's
            # watermark; the single-pool cache ignores the key).
            seq = Sequence(prompt, max_new_tokens, stop_token, cntl, done,
                           stream_id, tenant_id=tenant_id,
                           priority=priority)
            queued = sum(s.context_len() for s in self._iter_waiting())
            need = queued + len(prompt)
            shard = None
            if self.prefix is not None:
                # a cached prefix's blocks are already counted in pool
                # occupancy — only the suffix is new demand; prefix-hash
                # placement beats the seq-id route so the hit lands on
                # the shard holding the chain
                shard = self.prefix.route_shard(prompt)
                need = queued + max(1, len(prompt)
                                    - self.prefix.match_len(prompt))
            if not self.kv.can_admit(need, route_key=seq.seq_id,
                                     shard=shard):
                # before rejecting, ask the tree to give back LRU
                # refcount-1 chains — EOVERCROWDED semantics unchanged,
                # the watermark just sees fewer cache-held blocks
                if not (self.prefix is not None
                        and self.prefix.evict_for_admission(
                            need, shard=shard, route_key=seq.seq_id)
                        and self.kv.can_admit(need, route_key=seq.seq_id,
                                              shard=shard)):
                    self.kv.note_rejected()
                    g_serving_rejected.put(1)
                    return errors.EOVERCROWDED, None
            if self.qos is not None:
                # weighted fair-share lane: enqueue re-evaluates the QoS
                # admission predicate (deadline + tenant cap + limiter
                # ceiling) under the lock — check and append are one
                # decision
                code = self.qos.enqueue(seq)
                if code != 0:
                    if code == errors.ERPCTIMEDOUT:
                        g_serving_deadline_rejects.put(1)
                    g_serving_rejected.put(1)
                    return code, None
            else:
                self._waiting.append(seq)
            self._cv.notify()
        return 0, seq

    def _iter_waiting(self):
        """Every queued-but-unadmitted sequence (lock held): the FIFO
        deque, or the fair-share lanes when QoS is on."""
        if self.qos is not None:
            return self.qos.iter_waiting()
        return iter(self._waiting)

    @property
    def queue_depth(self) -> int:
        if self.qos is not None:
            return self.qos.total_depth()
        return len(self._waiting)

    @property
    def running_count(self) -> int:
        return len(self._running)

    @property
    def prefilling_count(self) -> int:
        """Sequences admitted and still mid-prompt (chunked prefill)."""
        return 0 if self._prefilling is None else 1

    # -------------------------------------------------- migration adoption
    def make_adopted_sequence(self, prompt: np.ndarray,
                              out_tokens: List[int], max_new_tokens: int,
                              stop_token: int = 0) -> Sequence:
        """Fabricate the destination-side Sequence for a migrated chain.
        The caller (MigrationReceiver) adopts the KV under the returned
        ``seq_id`` BEFORE handing it to :meth:`adopt_migrated` — the
        sequence must never be visible to the step loop without blocks."""
        seq = Sequence(prompt, max_new_tokens, stop_token)
        seq.out_tokens = list(out_tokens)
        seq.handoff_base = len(out_tokens)
        seq.adopted = True
        seq.state = STATE_RUNNING
        # out_tokens is never empty post-prefill: TTFT was recorded by
        # the source shard. t_last_token stays 0 so the first local
        # decode RESETS the ITL clock — transfer + slot-wait latency
        # belongs to the handoff, not this engine's inter-token gap
        seq.t_first_token = time.monotonic()
        seq.t_last_token = 0.0
        return seq

    def adopt_migrated(self, seq: Sequence, recovery: bool = False) -> bool:
        """Queue a migrated-in sequence for decode (its KV is already
        adopted). The step loop drains it into the running set under the
        same max_batch cap as admission; tokens buffer on the sequence
        until a client attaches. ``recovery`` additionally indexes it
        for prompt-match attach (shard-death retry traffic has no
        resume_seq_id — the original reply never arrived)."""
        with self._cv:
            if not self.running:
                return False
            seq.t_adopted = time.monotonic()
            self._adopted[seq.seq_id] = seq
            if recovery:
                key = (tuple(int(t) for t in seq.prompt),
                       int(seq.max_new_tokens), int(seq.stop_token))
                self._recover_index.setdefault(
                    key, collections.deque()).append(seq.seq_id)
            self._adopted_pending.append(seq)
            self._cv.notify()
        return True

    def _recover_match(self, prompt: np.ndarray, max_new_tokens: int,
                       stop_token: int) -> Optional[Sequence]:
        key = (tuple(int(t) for t in prompt), int(max_new_tokens),
               int(stop_token))
        with self._cv:
            dq = self._recover_index.get(key)
            while dq:
                rid = dq.popleft()
                if not dq:
                    self._recover_index.pop(key, None)
                    dq = None
                cand = self._adopted.get(rid)
                if cand is not None and not cand._attached:
                    return cand
        return None

    def _bind_attach(self, seq: Sequence, cntl, done, stream_id: int,
                     resume: bool) -> "tuple[int, Optional[Sequence]]":
        """Attach a client to a parked migrated sequence. Live sequences
        stream the tokens generated since the handoff point and keep
        decoding; already-finished ones complete the RPC immediately
        from the deferred result."""
        with self._cv:
            if seq._attached or seq.done is not None:
                return errors.EREQUEST, None
            seq._attached = True
            seq.resume_attach = resume
            seq.cntl = cntl
            seq.stream_id = stream_id
            deferred = seq._deferred
            base = seq.handoff_base if resume else 0
            replay = list(seq.out_tokens[base:])
            finished = seq.state == STATE_DONE
            if deferred is None:
                seq.done = done
            else:
                self._adopted.pop(seq.seq_id, None)
        if deferred is not None:
            code, reason = deferred
            try:
                if code != 0 and cntl is not None:
                    cntl.set_failed(code, reason)
                    done(None)
                else:
                    done(self._response_for(seq))
            except Exception:
                pass
            return 0, seq
        if replay and stream_id:
            # catch the client up on tokens decoded before it attached
            self._stream_delta(seq, replay, finished)
        return 0, seq

    # ------------------------------------------------------------ step loop
    def _loop(self) -> None:
        """Every instant of the loop lies in a span: its leaf spans (the
        innermost open one) partition the thread's time, which is how a
        profile attributes the device's idle gaps to the host."""
        _prof.register_current_thread("serving")
        self._spans = _prof.thread_spans()
        self._waits = _prof.thread_waits()
        try:
            while True:
                # admit's own time: the wait for the lock (an RPC thread
                # in submit holds it), prefix match, block allocation
                with _span("engine.admit") as sp:
                    with self._cv:
                        # a span a wait: a profiler session that starts
                        # mid-span never sees it, so none is long
                        while self.running and not self._has_work():
                            with _wait_span("engine.idle"):
                                self._cv.wait(self.config.idle_wait_s)
                        if not self.running:
                            return
                        admitted = self._admit_locked()
                    sp.note(admitted=len(admitted))
                if (not admitted and not self._running
                        and self._prefilling is None):
                    # waiting work exists but the pool is full — let
                    # in-flight frees land instead of spinning the step
                    with _wait_span("engine.pool_wait"):
                        time.sleep(0.002)
                    continue
                with _span("engine.step", step=self.steps,
                           batch=len(self._running)) as sp:
                    try:
                        with self.pool_gate:
                            self._step(admitted)
                    except Exception as e:  # engine must survive a bad step
                        for seq in self._live():
                            self._finish(seq, errors.EINTERNAL,
                                         f"step failed: {e}")
                        self._running, self._prefilling = [], None
                self.last_step_us = sp.elapsed_ns / 1000.0
                g_serving_step.record(self.last_step_us)
        finally:
            _prof.unregister_current_thread()

    def _has_work(self) -> bool:
        """Something to admit or to step (lock held)."""
        return bool(self._waiting or self._running or self._adopted_pending
                    or self._prefilling is not None
                    or (self.qos is not None and self.qos.total_depth()))

    def _admit_locked(self) -> List[Sequence]:
        """Pull waiting sequences into the running set — called between
        steps with the lock held: refills whenever a slot and budget
        exist."""
        cfg = self.config
        admitted: List[Sequence] = []
        # migrated-in chains first (already prefilled, zero prefill
        # cost) — capped by max_batch so the decode batch never exceeds
        # a size admission itself would dispatch
        while self._adopted_pending and len(self._running) < cfg.max_batch:
            seq = self._adopted_pending.popleft()
            self._running.append(seq)
            admitted.append(seq)
        # accepted-length is variable spend: a speculating sequence can
        # commit up to 1 + k tokens per step, so it reserves that many
        # budget slots, not one (a collapsed sequence is back to 1)
        budget = self._budget_left()
        if self.qos is not None:
            return self._admit_qos_locked(admitted, budget)
        # a sequence mid-prompt takes the step's budget and the next seat
        # of the batch: nothing is admitted behind it (FIFO order is kept)
        while (self._waiting and len(self._running) < cfg.max_batch
               and self._prefilling is None
               and budget >= self._admit_cost(self._waiting[0], budget)):
            seq = self._waiting[0]
            deadline = (getattr(seq.cntl, "deadline_mono", 0.0)
                        if seq.cntl else 0.0)
            if deadline and time.monotonic() >= deadline:
                self._waiting.popleft()
                g_serving_deadline_rejects.put(1)
                self._finish(seq, errors.ERPCTIMEDOUT,
                             "deadline expired in serving queue")
                continue
            try:
                self._alloc_for(seq)
            except KVCacheFull:
                # one retry after asking the tree for its LRU refcount-1
                # chains; still full means genuinely out of headroom
                if not (self.prefix is not None
                        and self.prefix.evict_for_admission(
                            seq.context_len(), route_key=seq.seq_id)):
                    break  # keep FIFO order; retry next step
                try:
                    self._alloc_for(seq)
                except KVCacheFull:
                    break
            self._waiting.popleft()
            budget -= self._admit_cost(seq, budget)
            self._mark_admitted(seq, admitted)
        return admitted

    def _mark_admitted(self, seq: Sequence, admitted: List[Sequence]) -> None:
        """``seq`` leaves the queue for the running set: its queue wait
        ends here (engine counters, and the request's rpcz span)."""
        seq.t_admit = time.monotonic()
        wait_us = (seq.t_admit - seq.t_submit) * 1e6
        self.admitted += 1
        self.queue_wait_us_sum += wait_us
        rspan = getattr(seq.cntl, "span", None)
        if rspan is not None:
            rspan.add_phase("serving_queue_us", wait_us)
        seq.state = STATE_RUNNING
        if self._chunk_unit and self._prefill_cost(seq) \
                > self._chunk_rows(self._budget_left()):
            # longer than what a step may prefill: a chunk a step, and into
            # the decode batch with its first token
            self._prefilling = seq
        else:
            self._running.append(seq)
        admitted.append(seq)
        g_serving_admitted.put(1)

    def _admit_qos_locked(self, admitted: List[Sequence],
                          budget: int) -> List[Sequence]:
        """Fair-share admission: each pull serves the backlogged tenant
        with the smallest virtual clock (stride scheduling meters the
        step's token budget by weight); the deadline is re-checked per
        sequence exactly as the FIFO path does, and a pool-full head
        keeps its turn for the next step's full budget."""
        cfg = self.config
        while len(self._running) < cfg.max_batch and self._prefilling is None:
            seq = self.qos.peek(budget,
                                lambda s: self._admit_cost(s, budget))
            if seq is None:
                break
            deadline = (getattr(seq.cntl, "deadline_mono", 0.0)
                        if seq.cntl else 0.0)
            if deadline and time.monotonic() >= deadline:
                self.qos.drop(seq)
                g_serving_deadline_rejects.put(1)
                self._finish(seq, errors.ERPCTIMEDOUT,
                             "deadline expired in serving queue")
                continue
            try:
                self._alloc_for(seq)
            except KVCacheFull:
                if not (self.prefix is not None
                        and self.prefix.evict_for_admission(
                            seq.context_len(), route_key=seq.seq_id)):
                    break
                try:
                    self._alloc_for(seq)
                except KVCacheFull:
                    break
            cost = self._admit_cost(seq, budget)
            self.qos.commit(seq, cost)
            budget -= cost
            self._mark_admitted(seq, admitted)
        return admitted

    def _decode_cost(self, seq: Sequence) -> int:
        """Iteration-budget cost of one decode step for ``seq``: the max
        tokens it can commit (1 + its current draft length)."""
        if self.config.spec_k <= 0:
            return 1
        k = seq.spec.k if seq.spec is not None else self.config.spec_k
        return 1 + k

    def _prefill_cost(self, seq: Sequence) -> int:
        """Iteration-budget cost of prefilling ``seq``: only the suffix
        past the cached prefix runs through the model (≥ 1 — the first
        token is always sampled by this engine)."""
        if self.prefix is None:
            return len(seq.prompt)
        if seq.prefix_len:  # already forked (allocated, not yet stepped)
            return max(1, len(seq.prompt) - seq.prefix_len)
        return max(1, len(seq.prompt) - self.prefix.match_len(seq.prompt))

    def _chunk_rows(self, budget: int) -> int:
        """Rows of ONE prompt a step may prefill out of ``budget``, for a
        model that can continue a prefill: what the budget leaves, rounded
        down to whole scan chunks and blocks."""
        return max(0, budget) // self._chunk_unit * self._chunk_unit

    def _budget_left(self) -> int:
        """The step's budget less one decode token a running sequence."""
        return self.config.token_budget - sum(self._decode_cost(s)
                                              for s in self._running)

    def _admit_cost(self, seq: Sequence, budget: int) -> int:
        """What admitting ``seq`` takes of this step's ``budget``: its
        prefill; for a prompt longer than a step may prefill of a model that
        can continue, its first chunk (at least one unit: less admits
        nothing)."""
        cost = self._prefill_cost(seq)
        if not self._chunk_unit:
            return cost
        # the step's chunk is cut from the budget at the step's start, so
        # that every step launches the ONE chunk size
        rows = self._chunk_rows(self._budget_left())
        if cost <= rows:
            return cost
        return rows if rows else budget + 1

    def _alloc_for(self, seq: Sequence) -> None:
        """Allocate ``seq``'s block table — forking the longest cached
        prefix chain when the radix tree has one (refcount++, zero
        copies), falling back to a cold allocation (prefix-hash placed
        on the sharded pool, so a first-seen prefix builds its chain on
        the shard later hits will route to)."""
        if self.prefix is None:
            self.kv.alloc_sequence(seq.seq_id, seq.context_len())
            return
        matched = self.prefix.fork(seq.seq_id, seq.prompt)
        if matched:
            seq.prefix_len = matched
            try:
                # grow the adopted chain to cover prompt + decode slot
                self.kv.extend_sequence(seq.seq_id, seq.context_len())
            except KVCacheFull:
                self.kv.free_sequence(seq.seq_id)  # unwind the fork
                seq.prefix_len = 0
                raise
            return
        shard = self.prefix.route_shard(seq.prompt)
        if shard is not None:
            self.kv.alloc_sequence(seq.seq_id, seq.context_len(),
                                   shard=shard)
        else:
            self.kv.alloc_sequence(seq.seq_id, seq.context_len())

    def _step(self, admitted: List[Sequence]) -> None:
        """One iteration inside the loop's ``engine.step`` span: a fused
        prefill per new sequence, then ONE fused program for the whole
        decode batch."""
        for seq in admitted:
            # an adopted chain arrived prefilled; a long prompt of a model
            # that can continue goes a chunk a step, below
            if not seq.adopted and seq is not self._prefilling:
                self._prefill_one(seq)
        if self._prefilling is not None:
            self._prefill_chunk()
        self._reap_finished()
        # ---- disaggregated handoff: a prefill-role engine ships every
        # live chain to the decode shard right after its first token; a
        # failed migration leaves the sequence here (local-decode
        # fallback), retried next step
        if self.config.role == ROLE_PREFILL and self.migrator is not None:
            self._migrate_handoff()
        batch = list(self._running)
        if batch:
            try:
                self._decode_batch(batch)
            except KVCacheFull:
                # mid-decode exhaustion: shed the youngest sequences until
                # the pool has headroom again — admission watermark should
                # make this rare, never fatal. Speculative headroom blocks
                # grabbed before the failure are handed back first so the
                # shed is no bigger than the non-speculative lane's.
                if self.config.spec_k > 0:
                    for s in batch:
                        try:
                            self.kv.truncate_sequence(s.seq_id,
                                                      s.context_len())
                        except KeyError:
                            pass
                # the youngest holder of pages: a sequence mid-prompt
                # first (it has yielded no token yet)
                shed, self._prefilling = (self._prefilling or batch[-1]), None
                self._finish(shed, errors.EOVERCROWDED,
                             "kv pool exhausted mid-decode")
        self._reap_finished()
        self.steps += 1
        self._occupancy_sum += len(batch)
        g_serving_steps.put(1)

    def _prefill_chunk(self) -> None:
        """This step's chunk of the sequence mid-prompt: what the budget
        leaves beside the decode rows (one unit at least: a prompt always
        advances); its last chunk yields the first token and the sequence
        joins the decode batch. A dead connection or a spent deadline frees
        its slot and pages here, as ``_reap_finished`` does for the batch."""
        seq = self._prefilling
        sock = getattr(seq.cntl, "_srv_socket", None)
        deadline = getattr(seq.cntl, "deadline_mono", 0.0) if seq.cntl else 0.0
        code, reason = 0, ""
        if sock is not None and getattr(sock, "failed", False):
            code, reason = (errors.EFAILEDSOCKET,
                            "connection failed mid-prompt")
        elif deadline and time.monotonic() >= deadline:
            g_serving_deadline_rejects.put(1)
            code, reason = errors.ERPCTIMEDOUT, "deadline expired mid-prompt"
        if code:
            self._prefilling = None
            self._finish(seq, code, reason)
            return
        rows = max(self._chunk_unit, self._chunk_rows(self._budget_left()))
        start = seq.prefilled
        self._prefill_one(seq, min(len(seq.prompt), start + rows))
        self.prefill_chunks += 1
        self.prefill_chunk_rows += seq.prefilled - start
        if seq.prefilled == len(seq.prompt):
            self._prefilling = None
            self._running.append(seq)

    def _prefill_one(self, seq: Sequence, end: Optional[int] = None) -> None:
        """Prefill ``seq``'s prompt, or (chunked prefill) its rows from
        ``seq.prefilled`` up to ``end``; the first token comes with the
        prompt's last row."""
        start, whole = seq.prefilled, len(seq.prompt)
        end = whole if end is None else end
        with _span("engine.prefill", seq=seq.seq_id, n=end - start,
                   start=start, of=whole) as sp:
            if start or end < whole:
                first = self.model.prefill_suffix(
                    seq.prompt[:end], self.kv.block_table(seq.seq_id), start)
            elif seq.prefix_len:
                # forked chain: cow-split the divergence block if shared,
                # then run only the suffix — hit TTFT is one decode-shaped
                # launch, not O(prompt) prefill
                self.kv.ensure_writable(seq.seq_id, seq.prefix_len)
                first = self.model.prefill_suffix(
                    seq.prompt, self.kv.block_table(seq.seq_id),
                    seq.prefix_len)
            else:
                first = self.model.prefill(
                    seq.prompt, self.kv.block_table(seq.seq_id))
            n_new = end - max(start, seq.prefix_len)
            g_serving_prefill_tokens.put(n_new)
            self.prefill_tokens += n_new
            seq.prefilled = end
            if end == whole:
                with _span("engine.commit", batch=1):
                    self._append_token(seq, first)
        rspan = getattr(seq.cntl, "span", None)
        if rspan is not None:
            rspan.add_phase("prefill_us", sp.elapsed_ns / 1000.0)

    def _decode_batch(self, batch: List[Sequence]) -> None:
        cfg = self.config
        spec_on = cfg.spec_k > 0 and hasattr(self.model, "verify_step")
        drafts: List[List[int]] = []
        _fault.maybe_sleep(_fault.hit("serving.decode.stall"))
        with _span("engine.decode_prep", batch=len(batch)) as prep:
            tokens = np.array([s.out_tokens[-1] for s in batch],
                              dtype=np.int32)
            # the step's input token (last sampled) is written at the end
            # of the current context, so capacity must cover context_len()
            # and the write position is context_len()-1
            positions = np.array([s.pos for s in batch], dtype=np.int32)
            if spec_on:
                # draft lane: host-side prompt-lookup over committed
                # history — zero device work before the one verify launch.
                # k is capped at remaining-1 (a full accept plus bonus
                # lands exactly on max_new_tokens) so the chain never
                # outgrows the admitted KV bound.
                vocab = getattr(self.model.config, "vocab", 0)
                for s in batch:
                    if s.spec is None:
                        s.spec = _spec.AdaptiveK(
                            cfg.spec_k, cfg.spec_collapse_after)
                    k = min(s.spec.k, max(0, s.max_new_tokens
                                          - len(s.out_tokens) - 1))
                    drafts.append(_spec.draft_tokens(
                        list(s.prompt) + s.out_tokens, k,
                        cfg.spec_ngram, vocab) if k > 0 else [])
            else:
                drafts = [[]] * len(batch)
            tables = [self.kv.extend_sequence(s.seq_id,
                                              s.context_len() + len(d))
                      for s, d in zip(batch, drafts)]
        # dispatch-count invariant: under an armed ledger, the whole
        # decode batch — across every mesh shard, and all k+1 verify rows
        # per sequence — must cost exactly ONE fused launch + ONE host sync
        audit = (getattr(self.model, "FUSED_STEP", False)
                 and getattr(self.kv, "_check", False))
        if audit:
            from brpc_tpu.tpu.device_lane import step_dispatch
            d_before = step_dispatch.snapshot()
        model_ns = self._span_ns("model.decode")
        if spec_on:
            outs = self.model.verify_step(tokens, positions, tables, drafts)
        else:
            nxt = self.model.decode_step(tokens, positions, tables)
        # the step's decode time, from the spans that covered it
        decode_us = (prep.elapsed_ns + self._span_ns("model.decode")
                     - model_ns) / 1000.0
        if audit:
            launches, _, syncs = step_dispatch.delta(
                d_before, step_dispatch.snapshot())
            assert (launches, syncs) == (1, 1), (
                f"decode step dispatched {launches} launches / "
                f"{syncs} host syncs for {len(batch)} seqs; the "
                f"step contract is exactly (1, 1)")
        shards_live: Dict[int, int] = {}
        for tbl in tables:
            sh = getattr(tbl, "shard", 0)
            shards_live[sh] = shards_live.get(sh, 0) + 1
        for sh, n_live in shards_live.items():
            st = self._shard_step.setdefault(sh, [0, 0.0, 0.0, 0])
            st[0] += 1
            st[1] += decode_us
            st[2] = decode_us
            st[3] += n_live
        with _span("engine.commit", batch=len(batch)):
            if spec_on:
                self._commit_speculative(batch, drafts, outs)
            else:
                for s, tok in zip(batch, nxt):
                    self._append_token(s, int(tok))
            for s in batch:
                rspan = getattr(s.cntl, "span", None)
                if rspan is not None:
                    rspan.add_phase("decode_us", decode_us / len(batch))

    def _span_ns(self, name: str) -> int:
        """Total time so far of the loop thread's spans called ``name``."""
        rec = self._spans.get(name)
        return rec[1] if rec else 0

    def _commit_speculative(self, batch: List[Sequence],
                            drafts: List[List[int]],
                            outs: List[np.ndarray]) -> None:
        """Greedy acceptance + KV rollback for one verify step. Per
        sequence: commit the longest draft prefix agreeing with the
        verifier's argmax plus the one bonus token (cut short at
        stop/max_new), stream ONE TokenDelta carrying the accepted
        count, roll rejected tail blocks back via ``truncate_sequence``
        (the garbage K/V left *inside* retained blocks sits past the
        committed context, and next step's contiguous verify rows
        rewrite every such position before any row can attend to it),
        and feed the AdaptiveK controller."""
        step_drafted = step_accepted = 0
        for s, d, m in zip(batch, drafts, outs):
            a, committed = _spec.accept_longest_prefix(d, m)
            ncommit = 0
            for tok in committed:
                self._append_token(s, tok, stream=False)
                ncommit += 1
                if s.state == STATE_DONE:
                    break
            accepted_sent = min(ncommit, a)
            self._stream_delta(s, committed[:ncommit],
                               s.state == STATE_DONE,
                               accepted=accepted_sent)
            # rejected rows wrote K/V past the committed context; drop
            # whole tail blocks now, let next step's writes mask the rest
            self.kv.truncate_sequence(s.seq_id, s.context_len())
            was_collapsed = s.spec.collapsed
            s.spec.update(len(d), a)
            # the +1 bonus is only a *speculative* gain when the step
            # drafted; an empty-draft step is a plain decode token
            bonus = (ncommit - accepted_sent) if d else 0
            step_drafted += len(d)
            step_accepted += a
            if self.spec_stats is not None:
                st = self.spec_stats
                st.drafted += len(d)
                st.accepted += a
                st.rejected += len(d) - a
                st.bonus += bonus
                if d:
                    st.spec_steps += 1
                if s.spec.collapsed and not was_collapsed:
                    st.collapsed_seqs += 1
            if d:
                _spec.g_serving_spec_draft_tokens.put(len(d))
                if a:
                    _spec.g_serving_spec_accepted_tokens.put(a)
                if len(d) - a:
                    _spec.g_serving_spec_rejected_tokens.put(len(d) - a)
            if bonus:
                _spec.g_serving_spec_bonus_tokens.put(bonus)
        _spec.note_step(step_drafted, step_accepted)

    # ----------------------------------------------------------- completion
    def _append_token(self, seq: Sequence, tok: int,
                      stream: bool = True) -> None:
        now = time.monotonic()
        if not seq.out_tokens:
            seq.t_first_token = now
            g_serving_ttft.record((now - seq.t_submit) * 1e6)
        elif seq.t_last_token:
            g_serving_itl.record((now - seq.t_last_token) * 1e6)
        seq.t_last_token = now
        seq.out_tokens.append(tok)
        self.tokens_generated += 1
        g_serving_tokens.put(1)
        finished = (len(seq.out_tokens) >= seq.max_new_tokens
                    or (seq.stop_token and tok == seq.stop_token))
        if stream:
            self._stream_delta(seq, [tok], finished)
        if finished:
            seq.finish_reason = ("stop_token"
                                 if seq.stop_token and tok == seq.stop_token
                                 else "length")
            seq.state = STATE_DONE

    def _stream_delta(self, seq: Sequence, toks: List[int],
                      done: bool, accepted: int = 0) -> None:
        if not seq.stream_id:
            return
        from brpc_tpu.proto import serving_pb2
        from brpc_tpu.rpc.stream import stream_write

        delta = serving_pb2.TokenDelta(
            seq_id=seq.seq_id, tokens=toks,
            step=len(seq.out_tokens), done=done, accepted=accepted)
        rc = stream_write(seq.stream_id, delta.SerializeToString())
        if rc != 0:
            seq.stream_id = 0  # stream died; finish via the RPC response

    def _reap_finished(self) -> None:
        still: List[Sequence] = []
        with _span("engine.reap") as sp:
            for seq in self._running:
                sock = getattr(seq.cntl, "_srv_socket", None)
                if sock is not None and getattr(sock, "failed", False):
                    # tunnel/connection died mid-generation: retriable
                    # error to the sequence, blocks back to the pool
                    self._finish(seq, errors.EFAILEDSOCKET,
                                 "connection failed mid-generation")
                elif seq.state == STATE_DONE:
                    self._finish(seq, 0, "")
                else:
                    still.append(seq)
            sp.note(finished=len(self._running) - len(still))
        self._running = still

    # ------------------------------------------------------------- handoff
    def _migrate_handoff(self) -> None:
        """Ship every live chain to the decode shard (runs on the engine
        thread between phases, so each sequence is quiescent). Successes
        complete the stage-1 RPC with the handoff meta; failures stay in
        the running set and decode locally."""
        moved = []
        for seq in list(self._running):
            if seq.state == STATE_DONE or seq.adopted:
                continue
            dest = self.migrator.migrate(seq, self.kv)
            if dest is not None:
                moved.append((seq, dest))
        if not moved:
            return
        gone = {id(s) for s, _ in moved}
        self._running = [s for s in self._running if id(s) not in gone]
        for seq, dest in moved:
            self._finish_handoff(seq, dest)

    def _finish_handoff(self, seq: Sequence, dest_seq_id: int) -> None:
        """Complete the stage-1 RPC: the reply's meta (finish_reason
        "handoff" + handoff_shard + the adopted seq_id) tells the client
        where its generation keeps running. The chain was released by
        the migrator on the destination's ACK — nothing to free here."""
        from brpc_tpu.proto import serving_pb2

        seq.state = STATE_DONE
        seq.finish_reason = "handoff"
        self._stream_delta(seq, [], True)  # stage-1 stream is complete
        done, seq.done = seq.done, None
        if done is None:
            return
        ttft_us = 0
        if seq.t_first_token:
            ttft_us = int((seq.t_first_token - seq.t_submit) * 1e6)
        resp = serving_pb2.GenerateResponse(
            tokens=seq.out_tokens, seq_id=dest_seq_id,
            prompt_len=len(seq.prompt), steps=len(seq.out_tokens),
            ttft_us=ttft_us, finish_reason="handoff",
            handoff_shard=self.migrator.dest_shard)
        try:
            done(resp)
        except Exception:
            pass

    def _drain_migrate(self) -> None:
        """stop()-path recovery: move live chains to the survivor. The
        client RPC still fails retriably (its engine IS going away), but
        the retry attaches to the migrated sequence on the destination —
        zero re-prefilled tokens."""
        with self._cv:
            live = [s for s in self._running
                    if s.state != STATE_DONE and not s.adopted]
        for seq in live:
            dest = self.migrator.migrate(seq, self.kv, recovery=True)
            if dest is None:
                continue  # the abort fan below will clean it up
            with self._cv:
                if seq in self._running:
                    self._running.remove(seq)
            self._finish(seq, errors.EFAILEDSOCKET,
                         "shard draining: sequence migrated to survivor "
                         "(retriable)")

    def _finish(self, seq: Sequence, code: int, reason: str) -> None:
        if code == 0 and self.prefix is not None and seq.out_tokens:
            # commit the fully-written blocks back into the radix tree
            # (insert-or-share) before the table drops; the last sampled
            # token's K/V was never written, hence the -1 valid length
            with _span("engine.prefix_commit", seq=seq.seq_id):
                self.prefix.commit(
                    seq.seq_id, list(seq.prompt) + seq.out_tokens,
                    len(seq.prompt) + len(seq.out_tokens) - 1)
        self.kv.free_sequence(seq.seq_id)
        if seq.state != STATE_DONE:
            seq.state = STATE_DONE
        if code == 0:
            g_serving_completed.put(1)
        else:
            g_serving_aborted.put(1)
        if seq.stream_id and code != 0:
            from brpc_tpu.rpc.stream import stream_close

            stream_close(seq.stream_id)
            seq.stream_id = 0
        with self._cv:
            if seq.adopted and seq.done is None and not seq._attached:
                # migrated-in with no client yet: park the result for
                # the stage-2/retry attach (blocks already freed above)
                seq._deferred = (code, reason)
                return
            self._adopted.pop(seq.seq_id, None)
        done, seq.done = seq.done, None
        if done is None:
            return
        try:
            if code != 0 and seq.cntl is not None:
                seq.cntl.set_failed(code, reason)
                done(None)
            else:
                done(self._response_for(seq))
        except Exception:
            pass

    def _response_for(self, seq: Sequence):
        from brpc_tpu.proto import serving_pb2

        ttft_us = 0
        if seq.t_first_token:
            ttft_us = int((seq.t_first_token - seq.t_submit) * 1e6)
        # a resume (stage-2) attach already received the prefill shard's
        # tokens in the stage-1 reply — return only the suffix decoded
        # here; a recovery attach replaces the lost reply entirely
        toks = (seq.out_tokens[seq.handoff_base:] if seq.resume_attach
                else seq.out_tokens)
        return serving_pb2.GenerateResponse(
            tokens=toks, seq_id=seq.seq_id,
            prompt_len=len(seq.prompt), steps=len(toks),
            ttft_us=ttft_us, finish_reason=seq.finish_reason or "length")

    def _live(self) -> List[Sequence]:
        """Every admitted sequence: the decode batch and the one
        mid-prompt."""
        return list(self._running) + ([self._prefilling]
                                      if self._prefilling is not None else [])

    def _abort_all_locked_out(self, code: int, reason: str) -> None:
        with self._cv:
            pending = (list(self._waiting) + self._live()
                       + list(self._adopted_pending))
            self._waiting.clear()
            self._running, self._prefilling = [], None
            self._adopted_pending.clear()
            if self.qos is not None:
                for seq in list(self.qos.iter_waiting()):
                    self.qos.drop(seq)
                    pending.append(seq)
        for seq in pending:
            self._finish(seq, code, reason)

    # ------------------------------------------------------------ visibility
    def _decode_snapshot(self) -> Optional[Dict[str, object]]:
        """The model's decode-attention counters (serving/model.py), for
        a model that keeps them: launches by path, and pages the rows'
        lengths covered against pages of the padded buckets."""
        counters = getattr(self.model, "decode_counters", None)
        if counters is None:
            return None
        out = dict(counters)
        out["live_share"] = round(
            out["decode_pages_live"] / max(1, out["decode_pages_bucket"]),
            4)
        return out

    def _moe_snapshot(self) -> Optional[Dict[str, object]]:
        """The model's expert-layer counters (serving/moe_model.py), for a
        model that keeps them: ``experts_held`` and, for decode and prefill
        apart, the token-expert pairs this chip computed, the distinct held
        experts hit, the most pairs of one expert (summed over
        layer-launches) and the layer-launches; for prefill also the
        attention layers that took the flash kernel and the blocked scan
        (``kernel_layers``, ``blocked_layers``); where the router has an
        output that computes nothing (serving/zaya_model.py), the rows it
        sent there (``skipped``)."""
        counters = getattr(self.model, "moe_counters", None)
        if counters is None:
            return None
        return {k: dict(v) if isinstance(v, dict) else v
                for k, v in counters.items()}

    def _scan_snapshot(self) -> Optional[Dict[str, int]]:
        """The model's selective-scan counters (serving/hybrid_model.py),
        for a model with Mamba layers: the prefill ``launches`` that ran
        the ``ssm_scan`` kernel and the ``rows`` handed to it (a launch's
        padded rows times its Mamba layers)."""
        counters = getattr(self.model, "scan_counters", None)
        return dict(counters) if counters is not None else None

    def _mla_snapshot(self) -> Optional[Dict[str, object]]:
        """The model's latent-attention counters (serving/glm_model.py),
        for a model whose pages hold one latent row a token: for decode and
        prefill apart the ``launches``, the live ``latent_rows`` they read
        (a launch's live context rows times its layers: what the decode
        kernel fetches, once each) and, for prefill, the ``expanded_rows``
        (context rows whose K and V a later chunk built again, times the
        layers)."""
        counters = getattr(self.model, "mla_counters", None)
        if counters is None:
            return None
        return {phase: dict(c) for phase, c in counters.items()}

    def _host_snapshot(self, loop_spans) -> Dict[str, object]:
        """The host side of the process, every number cumulative since
        start so that two snapshots difference (docs/serving.md "Reading a
        profile"): the loop thread's spans with their long closes, the CPU
        time inside those of them that wait by design, every thread's spans
        and CPU time by role (the native lane's threads too), the wait of
        the lane's events for the poller, and the collector's pauses."""
        us = _ns_to_us
        return {
            "wall_us": us(time.perf_counter_ns()),
            # the loop thread's: [self_us, long_n, long_self_us]
            "loop": {name: [us(rec[2]), rec[3], us(rec[4])]
                     for name, rec in loop_spans},
            # the loop's spans that wait by design (nothing to run, a full
            # pool, the device), with the CPU time used inside them: the
            # loop's CPU time (threads["serving"]) less these is what its
            # working spans cost
            "waits": {name: us(cpu)
                      for name, cpu in sorted(list(self._waits.items()))},
            # every thread's, live and ended: [count, self_us]
            "spans": {role: {name: [rec[0], us(rec[2])]
                             for name, rec in sorted(by_name.items())}
                      for role, by_name
                      in sorted(_prof.spans_by_role().items())},
            # [threads, cpu_us]: Python roles, lane.*, process, runtime
            "threads": {role: [n, us(cpu)] for role, (n, cpu) in sorted(
                _prof.cpu_by_role(_native_transport.lane_cpu()).items())},
            # [events, wait_us, max_us]: queued on a lane thread to picked
            # up by the poller
            "lane_wait": {kind: [n, us(wait), us(worst)]
                          for kind, (n, wait, worst)
                          in _native_transport.lane_wait().items()},
            # [collections, pause_us, max_us]
            "gc": [g if i == 0 else us(g)
                   for i, g in enumerate(_prof.gc_pauses())],
        }

    def snapshot(self) -> Dict[str, object]:
        kv = self.kv.snapshot()
        occ = (self._occupancy_sum / self.steps) if self.steps else 0.0
        spans = sorted(list(self._spans.items()))
        loop_ns = sum(rec[2] for _n, rec in spans) or 1
        migration = None
        if self.migrator is not None or self._migration_rx is not None:
            migration = {"parked": len(self._adopted)}
            if self.migrator is not None:
                migration["out"] = self.migrator.snapshot()
            if self._migration_rx is not None:
                migration["in"] = self._migration_rx.snapshot()
        return {
            "role": self.config.role,
            "migration": migration,
            "max_batch": self.config.max_batch,
            "token_budget": self.config.token_budget,
            "queue_depth": self.queue_depth,
            "running": self.running_count,
            # chunked prefill: sequences mid-prompt now, the launches that
            # were one chunk of a longer prompt and the rows they prefilled
            "prefilling": self.prefilling_count,
            "prefill_chunks": self.prefill_chunks,
            "prefill_chunk_rows": self.prefill_chunk_rows,
            "steps": self.steps,
            "tokens_generated": self.tokens_generated,
            "batch_occupancy_avg": round(occ, 3),
            "last_step_us": round(self.last_step_us, 1),
            "admitted": self.admitted,
            "queue_wait_us_sum": self.queue_wait_us_sum,
            "queue_wait_us_mean": round(
                self.queue_wait_us_sum / max(1, self.admitted), 1),
            # the loop thread's spans since start: [count, total_us,
            # self_us]; self times partition the loop's time, so their
            # shares say where the loop spends it
            "span_us": {name: [rec[0], round(rec[1] / 1000.0, 1),
                               round(rec[2] / 1000.0, 1)]
                        for name, rec in spans},
            "loop_share": {name: round(rec[2] / loop_ns, 4)
                           for name, rec in spans},
            "host": self._host_snapshot(spans),
            "step_us_p50": g_serving_step.latency_percentile(0.5),
            "step_us_p99": g_serving_step.latency_percentile(0.99),
            "ttft_us_p50": g_serving_ttft.latency_percentile(0.5),
            "ttft_us_p99": g_serving_ttft.latency_percentile(0.99),
            "itl_us_p50": g_serving_itl.latency_percentile(0.5),
            "shard_steps": {
                sh: {"steps": int(st[0]),
                     "avg_us": round(st[1] / st[0], 1) if st[0] else 0.0,
                     "last_us": round(st[2], 1),
                     "seq_steps": int(st[3])}
                for sh, st in sorted(self._shard_step.items())
            },
            "kv": kv,
            "prefix": (self.prefix.snapshot()
                       if self.prefix is not None else None),
            "decode": self._decode_snapshot(),
            "moe": self._moe_snapshot(),
            "scan": self._scan_snapshot(),
            "mla": self._mla_snapshot(),
            "spec": (dict(self.spec_stats.snapshot(),
                          k_max=self.config.spec_k)
                     if self.spec_stats is not None else None),
            "qos": (self.qos.snapshot()
                    if self.qos is not None else None),
        }
