"""The serving engine's writer thread: a decode step's frames, written while
the device runs the next step.

The loop thread owns a step from launch to commit; what it hands on is
ONE batch a step: every ``TokenDelta`` frame the decode commit produced,
as ``(seq, stream_id, tokens, step, done, accepted)``, and behind them
whatever completes those sequences' RPCs (a ``stream_close`` on an
abort, the ``done`` call with the response). One thread writes the
batches in the order they came, so a stream's frames keep their order
and its ``done`` frame precedes its response. Building, serializing and
writing a frame takes the interpreter between ctypes calls. The loop
hands the batch on as it next waits (``registry.on_wait``: in the next
step's ``model.sync``, after the launch), so the writer takes the
interpreter while the loop has no use for it, and not between two
launches, where the two would take turns at it.

A stream whose credit window is full blocks this thread, never the loop.
A write that fails marks the sequence (``stream_lost``); the writer
skips that stream from then on and the loop reads the mark at its next
reap.

The thread registers the role ``serving.writer``: its CPU time is a
contender's of the loop thread (``threads["serving.writer"]``), and its
spans (``engine.flush`` a batch, ``rpc.stream_write`` a frame,
``rpc.respond`` a response) land under that role.
"""

from __future__ import annotations

import collections
import threading
from time import perf_counter_ns
from typing import Deque, List, Optional, Tuple

from brpc_tpu.profiling import registry as _prof
from brpc_tpu.profiling.registry import span as _span
from brpc_tpu.proto import serving_pb2
from brpc_tpu.rpc import stream as _stream

ROLE = "serving.writer"


def write_frame(frame) -> bool:
    """Serialize one ``TokenDelta`` and write it to its stream; False if
    the stream is gone."""
    seq, stream_id, toks, step, done, accepted = frame
    delta = serving_pb2.TokenDelta(seq_id=seq.seq_id, tokens=toks, step=step,
                                   done=done, accepted=accepted)
    return _stream.stream_write(stream_id, delta.SerializeToString()) == 0


class FrameWriter:
    """One thread that writes what the loop hands it, batch after batch.
    A batch holds frames (tuples, see :func:`write_frame`) and callables
    (a completion of a sequence's RPC), run in order."""

    def __init__(self):
        self._cv = threading.Condition()
        # (handed off at, batch); None asks the thread to end
        self._queue: Deque[Optional[Tuple[int, list]]] = collections.deque()
        self._unfinished = 0      # batches handed off and not yet written
        self._thread: Optional[threading.Thread] = None
        # counters, cumulative: hand-offs, the frames in them, the time
        # from a hand-off to its batch's last write, and the most batches
        # still unwritten when one was handed off (above 0: the writer
        # fell a device step behind)
        self.batches = 0
        self.frames = 0
        self.lag_ns = 0
        self.backlog_max = 0

    def start(self) -> None:
        with self._cv:
            if self._thread is not None:
                return
            self._thread = threading.Thread(target=self._run, daemon=True,
                                            name="brpc-serving-writer")
        self._thread.start()

    def stop(self, timeout: float = 10.0) -> None:
        """Write what was handed off, then end the thread."""
        with self._cv:
            thread, self._thread = self._thread, None
            if thread is None:
                return
            self._queue.append(None)
            self._cv.notify()
        thread.join(timeout)

    def hand_off(self, batch: list) -> None:
        with self._cv:
            self.batches += 1
            self.frames += sum(1 for entry in batch if not callable(entry))
            self.backlog_max = max(self.backlog_max, self._unfinished)
            self._unfinished += 1
            self._queue.append((perf_counter_ns(), batch))
            self._cv.notify()

    def snapshot(self) -> List[float]:
        """``[batches, frames, lag_us_sum, backlog_max]``."""
        return [self.batches, self.frames, round(self.lag_ns / 1000.0, 1),
                self.backlog_max]

    def _run(self) -> None:
        _prof.register_current_thread(ROLE)
        try:
            while True:
                with self._cv:
                    while not self._queue:
                        self._cv.wait()
                    queued = self._queue.popleft()
                if queued is None:
                    return
                t0, batch = queued
                self._write(batch)
                self.lag_ns += perf_counter_ns() - t0
                with self._cv:
                    self._unfinished -= 1
        finally:
            _prof.unregister_current_thread()

    @staticmethod
    def _write(batch: list) -> None:
        with _span("engine.flush", n=len(batch)):
            for entry in batch:
                if callable(entry):
                    try:
                        entry()
                    except Exception:   # the writer outlives a bad done
                        pass
                    continue
                seq = entry[0]
                if not seq.stream_lost and not write_frame(entry):
                    seq.stream_lost = True
