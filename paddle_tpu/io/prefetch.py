"""DevicePrefetcher — the overlapped input pipeline (ISSUE 4 tentpole).

The DataLoader hands out numpy batches; before this module every consumer
serialized host batch prep, the H2D transfer and the device step into one
chain (the transfer happened inside the step call, so the device waited on
the host between steps).  ``DevicePrefetcher`` wraps any DataLoader/iterable and
keeps up to ``depth`` batches device-resident ahead of the consumer: a
background thread pulls host batches and issues ``jax.device_put`` (or
``mesh.put_global`` with the SPMD ``batch_spec`` sharding when a mesh is
given), so batch *k+1* is already on device while step *k* runs.

Contracts:

* **Bounded.**  At most ``depth`` batches sit in the buffer; the producer
  holds at most one more in flight, so the source is never more than
  ``depth + 1`` batches ahead of the consumer.
* **Clean end/err.**  Source exhaustion becomes a normal ``StopIteration``;
  a producer-side exception is re-raised in the consumer at the position
  it occurred.
* **No leaked threads.**  Dropping the iterator (``break``, GC) or calling
  ``close()`` stops the producer; its enqueue loop polls a stop event, so
  it can never block forever on a full buffer.
* **Zero syncs when warm.**  A warm buffer costs one ``Queue.get_nowait``
  per batch — no device sync, no new jit signature (the consumer-side
  train steps recognize the already-sharded arrays and skip re-transfer).

Telemetry: the always-on flight recorder gets a ``pipeline_stall`` event
whenever the consumer finds the buffer empty after warmup (the device is
about to wait on the host); with ``PADDLE_TPU_TELEMETRY=1`` the metrics
registry additionally carries the buffer-occupancy gauge and the
``host_input_wait_seconds`` counter (observability/steps.py).  ``stats()``
exposes the same numbers as plain floats for bench legs.
"""
from __future__ import annotations

import queue as queue_mod
import threading
import time

import numpy as np

from ..core.tensor import Tensor

# sentinel: the source is exhausted (producer -> consumer)
_END = object()


class _Failure:
    """Producer-side exception carried through the queue."""

    __slots__ = ("exc",)

    def __init__(self, exc):
        self.exc = exc


def _tree_put(obj, put):
    """Transfer every array leaf of a batch nest, keeping the container
    shape; leaves come back as Tensors over device arrays so both the hapi
    eager path and the SPMD step unwrap them without another copy."""
    if isinstance(obj, Tensor):
        return Tensor(put(obj._value), _internal=True)
    if isinstance(obj, (list, tuple)):
        return type(obj)(_tree_put(v, put) for v in obj)
    if isinstance(obj, dict):
        return {k: _tree_put(v, put) for k, v in obj.items()}
    if isinstance(obj, (np.ndarray, np.generic)) or hasattr(obj, "shape"):
        return Tensor(put(obj), _internal=True)
    return obj


def _tree_nbytes(obj) -> int:
    """Device bytes of a batch nest's array leaves (shape/dtype metadata
    only — never touches data, never syncs)."""
    if isinstance(obj, Tensor):
        obj = obj._value
    if isinstance(obj, (list, tuple)):
        return sum(_tree_nbytes(v) for v in obj)
    if isinstance(obj, dict):
        return sum(_tree_nbytes(v) for v in obj.values())
    shape = getattr(obj, "shape", None)
    dtype = getattr(obj, "dtype", None)
    if shape is None or dtype is None:
        return 0
    itemsize = getattr(dtype, "itemsize", None) or np.dtype(dtype).itemsize
    return int(np.prod(shape)) * int(itemsize)


class _PrefetchIter:
    """One epoch: a producer thread + a bounded queue.  Created fresh per
    ``iter(DevicePrefetcher)`` so epoch loops restart the pipeline."""

    def __init__(self, owner: "DevicePrefetcher", source):
        self._owner = owner
        self._q: queue_mod.Queue = queue_mod.Queue(maxsize=owner.depth)
        self._stop = threading.Event()
        self._warm = False
        self._done = False
        # HBM-ledger row: device bytes sitting in this buffer (batches
        # transferred but not yet consumed) declare their owner, so a
        # /debug/memory snapshot can name prefetch-held HBM
        from ..observability import perfscope
        self._ledger = perfscope.ledger().register(
            "prefetch", 0, detail=f"DevicePrefetcher buffer ({owner.name})")
        self._thread = threading.Thread(
            target=self._produce, args=(source,), daemon=True,
            name=f"prefetch-{owner.name}")
        self._thread.start()

    # -- producer ------------------------------------------------------------
    def _produce(self, source):
        put = self._owner._put
        try:
            for batch in source:
                if self._stop.is_set():
                    return
                dev = _tree_put(batch, put)
                if not self._enqueue(dev):
                    return
            self._enqueue(_END)
        except BaseException as e:  # noqa: BLE001 — re-raised consumer-side
            self._enqueue(_Failure(e))

    def _enqueue(self, item) -> bool:
        # bounded put that can always be woken by close(): never block
        # indefinitely on a full buffer the consumer abandoned
        while not self._stop.is_set():
            try:
                self._q.put(item, timeout=0.05)
            except queue_mod.Full:
                continue
            self._ledger.add(_tree_nbytes(item))
            self._owner._note_depth(self._q.qsize())
            return True
        return False

    # -- consumer ------------------------------------------------------------
    def __iter__(self):
        return self

    def __next__(self):
        if self._done:
            raise StopIteration
        owner = self._owner
        try:
            item = self._q.get_nowait()
        except queue_mod.Empty:
            # the train loop is about to wait on the host.  After warmup
            # that is a pipeline stall (producer slower than the device);
            # the cold first batch is expected and only counts as wait.
            stalled = self._warm
            t0 = time.perf_counter()
            item = self._blocking_get()
            owner._note_wait(time.perf_counter() - t0, stalled=stalled)
        self._warm = True
        self._ledger.add(-_tree_nbytes(item))
        owner._note_depth(self._q.qsize())
        if item is _END:
            self.close()
            raise StopIteration
        if isinstance(item, _Failure):
            self.close()
            raise item.exc
        owner._note_batch()
        return item

    def _blocking_get(self):
        while True:
            try:
                return self._q.get(timeout=1.0)
            except queue_mod.Empty:
                if not self._thread.is_alive():
                    # the Failure/_END protocol covers every normal exit;
                    # this guards against the producer dying unenqueued
                    raise RuntimeError(
                        "DevicePrefetcher producer thread died without "
                        "delivering a result")

    def close(self):
        """Stop the producer and release the buffer.  Idempotent; called on
        normal exhaustion, error, early exit and GC."""
        self._done = True
        self._stop.set()
        t = self._thread
        if t is not None and t is not threading.current_thread():
            t.join(timeout=5.0)
        self._ledger.release()     # buffered batches die with the iterator

    def __del__(self):
        try:
            self.close()
        except Exception:  # noqa: BLE001 — interpreter teardown
            pass


class DevicePrefetcher:
    """Wrap a DataLoader/iterable; yield device-resident batches ``depth``
    ahead of the consumer.

    With ``mesh`` given, every array leaf is placed with the SPMD
    ``batch_spec`` sharding (leading dim over the data axes) so the train
    step's ``shard_batch`` recognizes it and skips the re-transfer;
    ``stacked=True`` uses the ``run_steps`` layout instead (replicated
    leading K dim, data axes on dim 1).  Without a mesh, leaves go through
    plain ``jax.device_put``.

    Re-iterable: each ``iter()`` starts a fresh producer over
    ``iter(data)``; ``stats()`` aggregates across epochs.
    """

    def __init__(self, data, depth: int = 2, mesh=None,
                 stacked: bool = False, name: str = "prefetch"):
        self.data = data
        self.depth = max(1, int(depth))
        self.mesh = mesh
        self.stacked = bool(stacked)
        self.name = name
        self._last_iter: _PrefetchIter | None = None
        self._lock = threading.Lock()
        # plain-float stats, always on (bench reads them without telemetry)
        self.batches = 0
        self.wait_seconds = 0.0
        self.stalls = 0

    # -- placement -----------------------------------------------------------
    def _put(self, v):
        import jax
        if self.mesh is None:
            return jax.device_put(v)
        from jax.sharding import NamedSharding, PartitionSpec as P

        from ..distributed import mesh as mesh_mod
        from ..distributed.spmd import batch_spec
        ndim = int(np.ndim(v))
        if ndim == 0:
            spec = P()
        elif self.stacked:
            spec = P(None, *tuple(batch_spec(self.mesh, ndim - 1)))
        else:
            spec = batch_spec(self.mesh, ndim)
        return mesh_mod.put_global(v, NamedSharding(self.mesh, spec))

    # -- iteration -----------------------------------------------------------
    def __iter__(self):
        obs = self._obs()
        if obs.enabled():
            # pre-register the series at 0 so an exporter can tell "no
            # wait" (healthy overlap) from "not instrumented"
            obs.steps.record_input_wait(0.0, fn=self.name)
            obs.steps.set_prefetch_depth(0, fn=self.name)
        it = _PrefetchIter(self, iter(self.data))
        with self._lock:
            prev, self._last_iter = self._last_iter, it
        if prev is not None:
            prev.close()
        return it

    def __len__(self):
        return len(self.data)

    def close(self):
        with self._lock:
            it, self._last_iter = self._last_iter, None
        if it is not None:
            it.close()

    def stats(self) -> dict:
        # under the same lock the telemetry sinks take, so a reader
        # polling from another thread gets a consistent snapshot
        with self._lock:
            return {"batches": self.batches, "depth": self.depth,
                    "wait_seconds": self.wait_seconds,
                    "stalls": self.stalls}

    # -- telemetry sinks (called from both threads) --------------------------
    @staticmethod
    def _obs():
        from .. import observability
        return observability

    def _note_depth(self, qsize: int):
        obs = self._obs()
        if obs.enabled():
            obs.steps.set_prefetch_depth(qsize, fn=self.name)

    def _note_wait(self, seconds: float, stalled: bool):
        with self._lock:
            self.wait_seconds += seconds
            if stalled:
                self.stalls += 1
        obs = self._obs()
        if stalled:
            # always-on flight event: the device waited on the host
            obs.flight.record("pipeline_stall", self.name,
                              waited_ms=round(seconds * 1e3, 3),
                              depth=self.depth)
        if obs.enabled():
            obs.steps.record_input_wait(seconds, fn=self.name)
            if stalled:
                obs.steps.record_pipeline_stall(fn=self.name)

    def _note_batch(self):
        with self._lock:
            self.batches += 1
        obs = self._obs()
        if obs.enabled():
            obs.steps.record_prefetch_batch(fn=self.name)
