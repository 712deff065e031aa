"""Async device prefetch: overlap host batch construction with device compute.

``Prefetcher`` drains a ``(host_batch, cursor_after)`` iterator on a
background thread, runs ``place`` (the trainer's device_put with the mesh
batch sharding) on each batch, and keeps up to ``depth`` placed batches in a
bounded queue. The train loop's ``next()`` then returns an already-resident
batch while the thread builds the next ones — host batch construction leaves
the critical path.

Determinism contract: the prefetcher only *reorders work in time*, never the
stream — batches come off the queue in exactly the order the iterator
produced them, and the iterator itself is a pure function of its starting
cursor (packing.pack_batch). Trajectories with prefetch on and off are
therefore bit-identical (pinned in tests/test_pipeline.py, single-device and
dp=8).

Error/shutdown semantics: exceptions in the worker are re-raised at the
consumer's next ``next()``; ``close()`` (or context-manager exit) unblocks
and joins the thread, so a crashed train loop never leaks a producer.
"""
from __future__ import annotations

import queue
import threading

_DONE = object()


class Prefetcher:
    """Iterator over ``stream`` with ``depth`` batches built+placed ahead.

    ``depth == 0`` degrades to fully synchronous iteration (no thread) — the
    on/off switch is this one constructor argument, nothing else changes.
    """

    def __init__(self, stream, place=None, depth: int = 2):
        self._stream = iter(stream)
        self._place = place or (lambda x: x)
        self.depth = depth
        self._err: BaseException | None = None
        self._thread = None
        if depth > 0:
            self._q: queue.Queue = queue.Queue(maxsize=depth)
            self._stop = threading.Event()
            self._thread = threading.Thread(target=self._work, daemon=True,
                                            name="data-prefetch")
            self._thread.start()

    # ------------------------------------------------------------ worker
    def _work(self):
        try:
            for batch, cursor in self._stream:
                placed = self._place(batch)
                while not self._stop.is_set():
                    try:
                        self._q.put((placed, cursor), timeout=0.1)
                        break
                    except queue.Full:
                        continue
                if self._stop.is_set():
                    return
        except BaseException as e:  # noqa: BLE001 — surfaced to consumer
            self._err = e
        finally:
            while not self._stop.is_set():
                try:
                    self._q.put(_DONE, timeout=0.1)
                    break
                except queue.Full:
                    continue

    # ---------------------------------------------------------- consumer
    def __iter__(self):
        return self

    def __next__(self):
        if self._thread is None:  # synchronous mode
            batch, cursor = next(self._stream)
            return self._place(batch), cursor
        item = self._q.get()
        if item is _DONE:
            if self._err is not None:
                err, self._err = self._err, None
                raise err
            raise StopIteration
        return item

    def close(self):
        if self._thread is not None:
            self._stop.set()
            # drain so a blocked put() observes the stop event promptly
            try:
                while True:
                    self._q.get_nowait()
            except queue.Empty:
                pass
            self._thread.join()
            self._thread = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False
