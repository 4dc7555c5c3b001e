"""Background batch prefetching (host↔device overlap).

The reference overlaps data loading with compute via torch DataLoader
worker processes (reference utils.py:99-105). The TPU-native equivalent
is simpler: the jitted step is dispatched asynchronously, so the host is
free during device compute — all that is needed is to hide the HOST cost
of producing the next batch (HDF5 reads, tokenization, numpy gathers)
behind the in-flight step. One daemon thread fills a small queue;
`prefetch()` wraps any batch iterator.

Exceptions raised by the source iterator are re-raised at the consuming
`next()` (not lost on the thread), and `close()` / generator GC stops the
thread promptly.
"""

from __future__ import annotations

import queue
import threading
from typing import Iterator

from proteinbert_tpu.obs.tracing import span

_SENTINEL = object()


class PrefetchIterator:
    """Iterator view over `source` with `depth` batches produced ahead.

    `batches` counts deliveries (telemetry's `data_batches_total`). How
    long the CONSUMER sat blocked on an empty queue — the host input
    pipeline failing to stay ahead of the device — is the consumer's to
    time: the trainer's `train.data_wait` span around its `next()` is
    the one clock, and feeds `data_wait_seconds`."""

    def __init__(self, source: Iterator, depth: int = 2):
        if depth < 1:
            raise ValueError(f"depth must be >= 1, got {depth}")
        self._q: "queue.Queue" = queue.Queue(maxsize=depth)
        self._stop = threading.Event()
        self._error = None
        self._done = False
        self._source = source
        self.batches = 0
        self._thread = threading.Thread(target=self._fill, daemon=True)
        self._thread.start()

    def _fill(self):
        try:
            source = iter(self._source)
            while True:
                # The producer's own time a batch, on the span spine
                # beside the trainer's `train.data_wait` (obs/tracing).
                with span("data.produce"):
                    item = next(source, _SENTINEL)
                if item is _SENTINEL:
                    break
                while not self._stop.is_set():
                    try:
                        self._q.put(item, timeout=0.1)
                        break
                    except queue.Full:
                        continue
                if self._stop.is_set():
                    return
        except BaseException as e:  # re-raised on the consumer side
            self._error = e
        while not self._stop.is_set():
            try:
                self._q.put(_SENTINEL, timeout=0.1)
                return
            except queue.Full:
                continue

    def __iter__(self):
        return self

    def _raise_pending_error(self):
        """Re-raise the producer's exception ON THE CONSUMER — with its
        ORIGINAL traceback (the exception object carries the producer
        frame's __traceback__, so the report points at the raising line
        inside the source iterator, not at this queue plumbing)."""
        err, self._error = self._error, None
        self._done = True
        raise err.with_traceback(err.__traceback__)

    def __next__(self):
        if self._done:
            raise StopIteration
        while True:
            try:
                item = self._q.get(timeout=0.5)
                break
            except queue.Empty:
                # The fill thread can only be gone after delivering the
                # sentinel OR after close(); either way nothing more is
                # coming — never block a training loop forever. A
                # producer that DIED on an exception must surface that
                # exception here, not a generic StopIteration that
                # reads as clean end-of-data.
                if self._stop.is_set() or not self._thread.is_alive():
                    if self._error is not None:
                        self._raise_pending_error()
                    self._done = True
                    raise StopIteration from None
        if item is _SENTINEL:
            if self._error is not None:
                self._raise_pending_error()
            self._done = True
            raise StopIteration
        self.batches += 1
        return item

    def close(self):
        self._stop.set()

    def __del__(self):
        self.close()


def prefetch(source: Iterator, depth: int = 2) -> PrefetchIterator:
    """Wrap `source` so its batches are produced `depth` ahead on a
    background thread. depth=0 semantics (no-op) are the caller's choice —
    pass the source through unwrapped."""
    return PrefetchIterator(source, depth)
