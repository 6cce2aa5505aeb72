"""Device prefetch: overlap host→device transfer with the running step.

The training loops call ``jax.device_put(next(loader), sharding)``
synchronously: the accelerator idles through the host-side batch
assembly AND the PCIe transfer of every batch.  The torch side
hides this with pinned-memory DataLoader workers; the JAX-native
equivalent is simpler — ``device_put`` is asynchronous (it returns
before the transfer completes, like every dispatch), so it suffices to
issue the put for batch ``k+1`` while the step for batch ``k`` runs.
``prefetch_to_device`` does exactly that with a ``depth``-deep deque;
a background thread drains the (possibly blocking) host iterator so a
slow ``next()`` — corpus gather, preprocessing — also overlaps.

Usage::

    for batch in prefetch_to_device(loader, token_sharding(mesh)):
        state, loss = step(state, batch)

Order-preserving, exhausts the source exactly once, re-raises the
source's exception at the matching position.  ``depth=2`` (double
buffering) is enough to hide transfer behind any step that outlasts it;
deeper only helps jittery sources.
"""

from __future__ import annotations

import collections
import dataclasses
import queue
import threading
import time
from typing import Iterable, Iterator, Optional

_SENTINEL = object()


@dataclasses.dataclass
class PrefetchStats:
    """Producer/consumer wait accounting for one prefetch stream.

    ``producer_wait_s``: time the background drain thread spent blocked on
    a FULL host queue (the consumer — i.e. the step — is the bottleneck;
    harmless).  ``consumer_wait_s``: time the consumer spent blocked on an
    EMPTY queue (the data source is the bottleneck; this is real data
    stall and is additionally recorded as ``data_wait`` telemetry spans,
    so it lands in the goodput report's ``data`` component).  Totals are
    also published as one ``prefetch_stats`` telemetry event when the
    stream ends."""

    producer_wait_s: float = 0.0
    consumer_wait_s: float = 0.0
    batches: int = 0


def prefetch_to_device(
    source: Iterable,
    sharding=None,
    *,
    depth: int = 2,
    host_buffer: int = 2,
    put_fn=None,
    stats: Optional[PrefetchStats] = None,
) -> Iterator:
    """Yield ``device_put(batch, sharding)`` for each batch of ``source``,
    keeping up to ``depth`` transfers in flight ahead of the consumer.

    ``sharding``: anything ``jax.device_put`` accepts (NamedSharding, a
    pytree of them, a Device, or None for the default placement).
    ``host_buffer``: how many raw batches the background thread may pull
    ahead of the transfer queue (bounds host memory for fast sources).
    ``put_fn``: replaces ``device_put`` wholesale (e.g. the multi-host
    ``device_put_global`` assembly, or a zigzag permutation composed with
    the transfer); called from the CONSUMER thread, dispatch-async like
    device_put.
    ``stats``: a caller-owned :class:`PrefetchStats` accumulating the
    producer/consumer queue wait times (always measured; the object just
    exposes them).  Consumer stalls are also streamed as ``data_wait``
    telemetry spans and the totals as a ``prefetch_stats`` event.

    Complementary to :class:`tpudist.data.native_loader.PrefetchingLoader`
    (which overlaps HOST-side batch assembly): stack them to hide both
    the gather and the transfer.
    """
    if depth < 1:
        raise ValueError(f"depth must be >= 1, got {depth}")
    if host_buffer < 1:
        # queue.Queue(0) would mean UNBOUNDED — the opposite of the
        # documented host-memory bound.
        raise ValueError(f"host_buffer must be >= 1, got {host_buffer}")

    q: queue.Queue = queue.Queue(maxsize=host_buffer)
    stop = threading.Event()
    if stats is None:
        stats = PrefetchStats()

    def put(item) -> bool:
        t0 = time.monotonic()
        try:
            q.put_nowait(item)  # fast path: no wait, no clock cost beyond t0
            return True
        except queue.Full:
            pass
        while not stop.is_set():
            try:
                q.put(item, timeout=0.1)
                stats.producer_wait_s += time.monotonic() - t0
                return True
            except queue.Full:
                continue
        stats.producer_wait_s += time.monotonic() - t0
        return False

    def drain():
        try:
            for item in source:
                if not put(item):
                    return  # consumer abandoned the iterator
        except BaseException as e:  # re-raised at the consumer's position
            put((_SENTINEL, e))
            return
        put((_SENTINEL, None))

    t = threading.Thread(target=drain, daemon=True,
                         name="tpudist-prefetch")
    t.start()

    def puts() -> Iterator:
        from tpudist import telemetry

        while True:
            tele = telemetry.active()
            t0 = time.monotonic()
            item = q.get()
            wait = time.monotonic() - t0
            stats.consumer_wait_s += wait
            if tele is not None:
                # The consumer-side stall IS the data stall: feed it to
                # the goodput report's `data` component (auto-nested if a
                # caller's own data_wait span wraps this iterator).
                tele.record_span("data_wait", t0, wait)
            if isinstance(item, tuple) and len(item) == 2 \
                    and item[0] is _SENTINEL:
                err: Optional[BaseException] = item[1]
                if err is not None:
                    raise err
                return
            stats.batches += 1
            if put_fn is not None:
                yield put_fn(item)
            else:
                import jax  # lazy: tpudist.data stays importable w/o jax

                yield (jax.device_put(item, sharding)
                       if sharding is not None else jax.device_put(item))

    buf: collections.deque = collections.deque()
    it = puts()
    err: Optional[BaseException] = None
    try:
        while True:
            try:
                x = next(it)
            except StopIteration:
                break
            except BaseException as e:
                # deliver the batches that preceded the failure, THEN
                # re-raise at the matching position
                err = e
                break
            buf.append(x)
            if len(buf) > depth:
                yield buf.popleft()
        while buf:
            yield buf.popleft()
        if err is not None:
            raise err
    finally:
        # Abandoned mid-iteration (or done): release the drain thread —
        # its bounded put polls this flag, so it exits promptly instead
        # of pinning the source and queue buffers.
        stop.set()
        # Stats event from the finally, not the sentinel branch: the
        # common exit is the training loop breaking at its iteration
        # budget with the source still live, and the wait totals must
        # reach the report on that path too.
        from tpudist import telemetry

        telemetry.event(
            "prefetch_stats",
            producer_wait_s=round(stats.producer_wait_s, 6),
            consumer_wait_s=round(stats.consumer_wait_s, 6),
            batches=stats.batches,
        )
