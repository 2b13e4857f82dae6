"""Throughput subsystem for the task-batched LITE engine.

Two pieces, both pure plumbing around the deterministic ``batch_at(step)``
contract the fault-tolerant loop already relies on:

* :class:`Prefetcher` — a double-buffered background-thread host->device
  pipeline.  A worker thread materializes ``batch_at(step)`` for steps in
  order and pushes device-committed batches into a bounded queue, so
  collation + H2D transfer overlap with the device compute of the
  previous step.  The consumer side is strictly sequential (``get(step)``
  asserts the step index), which is what keeps bit-exact checkpoint
  resume trivially true: the thread is just a lookahead evaluator of the
  same pure function the synchronous loop would call.

* :class:`BucketedStepCache` — a per-padded-shape AOT-compiled train-step
  cache.  Ragged task streams collated against a planned bucket set
  (:func:`repro.data.episodic.plan_buckets`) produce a small closed set of
  shapes; this cache compiles one executable per shape key (optionally
  with params/opt-state buffer donation) and exposes ``compile_count`` so
  tests and monitors can assert the compile rate stays flat.
"""
from __future__ import annotations

import queue
import threading
import time
from typing import Any, Callable, Dict, Optional

import jax

PyTree = Any

_DONE = object()        # worker finished the requested range
_FAILED = object()      # worker raised; error in Prefetcher._err


class Prefetcher:
    """Background lookahead over a deterministic ``batch_at(step)`` stream.

    ``depth`` bounds how many batches may be in flight (2 = classic double
    buffering: one being consumed, one being built).  Batches are
    ``jax.device_put`` from the worker thread, so the transfer itself also
    overlaps compute.  A failing ``batch_at`` is retried up to ``retries``
    times with exponential backoff (``backoff_s * 2**attempt``; 0 = no
    wait, which is what deterministic tests use) — transient data-source
    faults heal in place and the delivered stream is unchanged; only an
    error that survives every retry is re-raised from ``get``.
    ``retries_used`` counts the retries actually spent (surfaced in
    ``TrainResult.data_retries``); ``wait_s`` sums the seconds (by
    ``clock``) that ``get`` spent blocked on the worker.  Under the
    profiler the worker's batch production shows as
    ``repro.prefetch.produce`` spans and the consumer's waits as
    ``repro.prefetch.get``.  Always ``close()`` (the training loop does so
    in a ``finally``) so a preempted run doesn't leak the thread.
    """

    def __init__(self, batch_at: Callable[[int], PyTree], start: int,
                 stop: int, depth: int = 2, to_device: bool = True,
                 put: Optional[Callable[[PyTree], PyTree]] = None,
                 retries: int = 0, backoff_s: float = 0.05,
                 clock: Callable[[], float] = time.monotonic):
        """``put`` overrides the default ``jax.device_put`` — pass a
        sharded transfer (e.g. ``device_put`` with a ``NamedSharding``
        over the task axis) so batches land in the mesh layout the
        sharded step consumes, instead of on device 0 with a resharding
        copy inside the step dispatch."""
        if depth < 1:
            raise ValueError(f"prefetch depth must be >= 1, got {depth}")
        self._q: queue.Queue = queue.Queue(maxsize=depth)
        self._stop_evt = threading.Event()
        self._err: Optional[BaseException] = None
        self._next = start
        self._batch_at = batch_at
        self._to_device = to_device
        self._device_put = put if put is not None else jax.device_put
        self._retries = retries
        self._backoff_s = backoff_s
        self.retries_used = 0
        self.wait_s = 0.0
        self._clock = clock
        self._thread = threading.Thread(
            target=self._worker, args=(start, stop), daemon=True,
            name="batch-prefetcher")
        self._thread.start()

    def _put(self, item) -> bool:
        while not self._stop_evt.is_set():
            try:
                self._q.put(item, timeout=0.05)
                return True
            except queue.Full:
                continue
        return False

    def _fetch(self, s: int) -> PyTree:
        """``batch_at(s)`` with bounded exponential-backoff retry — the
        tolerance half of the ``data.transient`` fault site.  The wait uses
        the stop event so ``close()`` interrupts a backoff immediately."""
        delay = self._backoff_s
        for attempt in range(self._retries + 1):
            try:
                return self._batch_at(s)
            except Exception:
                if attempt == self._retries or self._stop_evt.is_set():
                    raise
                self.retries_used += 1
                if delay > 0:
                    self._stop_evt.wait(delay)
                    delay *= 2

    def _worker(self, start: int, stop: int) -> None:
        try:
            for s in range(start, stop):
                if self._stop_evt.is_set():
                    return
                with jax.profiler.TraceAnnotation("repro.prefetch.produce"):
                    batch = self._fetch(s)
                    if self._to_device:
                        batch = self._device_put(batch)
                if not self._put((s, batch)):
                    return
            self._put(_DONE)
        except BaseException as e:  # noqa: BLE001 — delivered via get()
            self._err = e
            self._put(_FAILED)

    def get(self, step: int) -> PyTree:
        """Next batch; blocks until the worker has it.  Strictly sequential
        — the loop must consume exactly the steps the prefetcher was built
        for, in order."""
        if step != self._next:
            raise ValueError(f"prefetcher is sequential: expected step "
                             f"{self._next}, got {step}")
        t0 = self._clock()
        with jax.profiler.TraceAnnotation("repro.prefetch.get"):
            while True:
                try:
                    item = self._q.get(timeout=0.1)
                    break
                except queue.Empty:
                    if self._err is not None:
                        raise self._err
                    if not self._thread.is_alive():
                        raise RuntimeError("prefetcher thread died without "
                                           "delivering a batch")
        self.wait_s += self._clock() - t0
        if item is _FAILED:
            raise self._err
        if item is _DONE:
            raise ValueError(f"prefetcher exhausted before step {step}")
        s, batch = item
        assert s == step, (s, step)
        self._next += 1
        return batch

    def close(self) -> None:
        self._stop_evt.set()
        # unblock a worker stuck on a full queue
        try:
            while True:
                self._q.get_nowait()
        except queue.Empty:
            pass
        self._thread.join(timeout=5.0)


def _aval_key(args) -> tuple:
    """Hashable key: pytree structure (static fields included) + the
    shape/dtype of every array leaf — exactly what XLA specializes on."""
    leaves, treedef = jax.tree.flatten(args)
    return (treedef,
            tuple((tuple(getattr(l, "shape", ())),
                   str(getattr(l, "dtype", type(l).__name__)))
                  for l in leaves))


class BucketedStepCache:
    """Per-shape AOT-compiled cache for a train-step-like callable.

    ``jax.jit`` already retraces per shape; what the cache adds is (a) an
    exact, inspectable ``compile_count`` (a flat counter across a ragged
    stream is the bucketing policy working), (b) explicit lowering so the
    compile happens at a known point, and (c) optional buffer donation of
    the leading ``donate_argnums`` arguments (params/opt state for the
    task-batched step signature ``(params, opt_state, batch, key)``).
    """

    def __init__(self, step_fn: Callable, donate: bool = False,
                 donate_argnums: tuple = (0, 1)):
        self._jit = jax.jit(step_fn,
                            donate_argnums=donate_argnums if donate else ())
        self._compiled: Dict[tuple, Callable] = {}

    @property
    def compile_count(self) -> int:
        return len(self._compiled)

    def __call__(self, *args):
        key = _aval_key(args)
        fn = self._compiled.get(key)
        if fn is None:
            fn = self._jit.lower(*args).compile()
            self._compiled[key] = fn
        return fn(*args)
