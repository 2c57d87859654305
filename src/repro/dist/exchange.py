"""The transport exchange: transports, and the exchange a worker rank binds.

The op layer (:class:`~repro.core.executor.Executor`) is the same on every
backend and reaches other partitions only through an exchange with four
operations. The local executor binds the in-memory
:class:`~repro.core.executor.LocalExchange`; a worker rank
(:class:`~repro.dist.worker.WorkerRuntime`) binds
:class:`TransportExchange`, which moves page blocks between ranks:

* ``shuffle`` — hash-partition shuffle of a JOIN side: every rank splits
  its batches by key hash, sends each peer that peer's bucket of
  sub-batches and keeps its own bucket unserialized;
* ``broadcast`` — a JOIN's build side: every rank replicates its batches
  to all peers (serialized once, shipped P-1 times);
* ``merge_partials`` — the AGG partials: split by key hash, the peers'
  shares sent as packed blocks, merged in rank order;
* ``gather`` — everyone ships to one root (TOPK's and SORT's merge at
  rank 0, OUTPUT's collect at the driver).

Three transports behind one interface:

* :class:`ThreadTransport` — per-worker in-process mailboxes;
* :class:`ProcessTransport` — a duplex pipe per forked worker, with the
  driver routing worker→worker messages (a star);
* :class:`SocketTransport` — one framed TCP connection to the driver,
  which routes worker→worker frames over the same star — the true
  multi-host transport (workers may live on other machines; see
  ``python -m repro.dist.worker --connect host:port``).

All move the same serialized page blocks, so ``shuffle_bytes`` measures
identical traffic regardless of the worker kind. ``recv`` buffers by
(source, tag): the exchange schedule is SPMD-deterministic, but message
*arrival* order is not. A ``recv`` raises :class:`PeerAborted` when the
driver broadcasts ABORT.
"""
from __future__ import annotations

import queue
from collections import deque
from typing import Any, Dict, List, Optional, Tuple

from repro.core.executor import ExecStats
from repro.core.relops import (AggMap, AggSpec, bucket_by_hash,
                               concat_batches, count_shuffle, exchange_span)
from repro.dist.protocol import (ABORT, DRIVER, decode_agg_map, decode_batch,
                                 encode_agg_map, encode_batch, read_frame,
                                 write_frame)
from repro.obs.trace import current
from repro.objectmodel.vectorlist import VectorList

__all__ = ["PeerAborted", "ThreadTransport", "ProcessTransport",
           "SocketTransport", "TransportExchange"]


class PeerAborted(RuntimeError):
    """Raised inside a worker's ``recv`` when the driver broadcasts ABORT
    (a peer failed): the worker must stop waiting for messages that will
    never arrive and unwind."""


class ThreadTransport:
    """In-process transport: one queue per worker plus the driver's."""

    def __init__(self, rank: int, worker_queues: List["queue.SimpleQueue"],
                 driver_queue: "queue.SimpleQueue"):
        self.rank = rank
        self._queues = worker_queues
        self._driver = driver_queue
        self._buffer: Dict[Tuple[int, str], deque] = {}

    def send(self, dst: int, tag: str, msg: Any) -> None:
        q = self._driver if dst == DRIVER else self._queues[dst]
        q.put((self.rank, tag, msg))

    def recv(self, src: int, tag: str) -> Any:
        want = (src, tag)
        buf = self._buffer.get(want)
        if buf:
            return buf.popleft()
        while True:
            got_src, got_tag, msg = self._queues[self.rank].get()
            if got_src == DRIVER and got_tag == ABORT:
                raise PeerAborted("a peer worker failed; aborting")
            if (got_src, got_tag) == want:
                return msg
            self._buffer.setdefault((got_src, got_tag),
                                    deque()).append(msg)


class ProcessTransport:
    """Forked-worker transport: a duplex pipe to the driver, which routes
    worker→worker messages (see ``driver._ProcessRuntime``)."""

    def __init__(self, rank: int, conn):
        self.rank = rank
        self._conn = conn
        self._buffer: Dict[Tuple[int, str], deque] = {}

    def send(self, dst: int, tag: str, msg: Any) -> None:
        self._conn.send((self.rank, dst, tag, msg))

    def recv(self, src: int, tag: str) -> Any:
        want = (src, tag)
        buf = self._buffer.get(want)
        if buf:
            return buf.popleft()
        while True:
            got_src, got_tag, msg = self._conn.recv()
            if got_src == DRIVER and got_tag == ABORT:
                raise PeerAborted("a peer worker failed; aborting")
            if (got_src, got_tag) == want:
                return msg
            self._buffer.setdefault((got_src, got_tag),
                                    deque()).append(msg)


class SocketTransport:
    """TCP transport: one length-prefixed framed connection to the driver,
    which routes worker→worker frames (the same star topology as the fork
    router — peers never dial each other, so workers only need to reach
    the driver's advertised host:port). Page payloads cross as raw bytes
    (no pickle copy; see :mod:`repro.dist.protocol`). The socket has a
    single writer — the worker's own thread."""

    def __init__(self, rank: int, sock):
        self.rank = rank
        self.sock = sock
        self._buffer: Dict[Tuple[int, str], deque] = {}

    def send(self, dst: int, tag: str, msg: Any) -> None:
        write_frame(self.sock, self.rank, dst, tag, msg)

    def recv(self, src: int, tag: str) -> Any:
        want = (src, tag)
        buf = self._buffer.get(want)
        if buf:
            return buf.popleft()
        while True:
            frame = read_frame(self.sock)
            if frame is None:
                raise PeerAborted(
                    "driver connection closed mid-query; aborting")
            got_src, _dst, got_tag, msg = frame
            if got_src == DRIVER and got_tag == ABORT:
                raise PeerAborted("a peer worker failed; aborting")
            if (got_src, got_tag) == want:
                return msg
            self._buffer.setdefault((got_src, got_tag),
                                    deque()).append(msg)

    def close(self) -> None:
        try:
            self.sock.close()
        except OSError:
            pass


# ------------------------------------------------------------- exchange
class TransportExchange:
    """The exchange of one rank among ``P``: the op layer
    (:class:`~repro.core.executor.Executor`) hands it a one-element list,
    this rank's partition, and rows cross to the other ranks as encoded
    page blocks over the transport ``tr``, tagged by TCAP op index so
    concurrent exchanges of one program never interleave. Every
    operation returns this rank's one-element result; ``shuffle_bytes``
    counts the encoded blocks sent to peers (the own share stays
    unserialized: locality is free)."""

    def __init__(self, tr, P: int):
        self.tr = tr
        self.P = P

    def shuffle(self, parts, hash_name: str, tag: str,
                stats: ExecStats) -> List[VectorList]:
        """Hash-partition shuffle by the column ``hash_name`` (``hash %
        P``): the rows that land here, concatenated in (source rank,
        batch) order. The split, the transfers and the concatenation all
        sit inside the exchange span."""
        rank, P = self.tr.rank, self.P
        with exchange_span("shuffle", tag, stats):
            buckets = bucket_by_hash(parts[0], hash_name, P)
            for dst in range(P):
                if dst == rank:
                    continue
                blocks = [encode_batch(vl) for vl in buckets[dst]]
                count_shuffle(stats, sum(b.nbytes for b in blocks))
                self.tr.send(dst, tag, blocks)
            inbox = [buckets[rank] if src == rank else
                     [decode_batch(b) for b in self.tr.recv(src, tag)]
                     for src in range(P)]
            return [concat_batches([vl for src in inbox for vl in src])]

    def broadcast(self, parts, tag: str,
                  stats: ExecStats) -> List[VectorList]:
        """Replicate this rank's batches to every peer (serialized once,
        shipped P-1 times); the build side is every rank's batches in
        rank order."""
        rank = self.tr.rank
        with exchange_span("bcast", tag, stats):
            blocks = None
            for dst in range(self.P):
                if dst == rank:
                    continue
                if blocks is None:
                    blocks = [encode_batch(vl) for vl in parts[0]]
                count_shuffle(stats, sum(b.nbytes for b in blocks))
                self.tr.send(dst, tag, blocks)
            srcs = [parts[0] if src == rank else
                    [decode_batch(b) for b in self.tr.recv(src, tag)]
                    for src in range(self.P)]
        return [concat_batches([vl for src in srcs for vl in src])]

    def merge_partials(self, spec: AggSpec, partials: List[AggMap],
                       tag: str, stats: ExecStats) -> List[AggMap]:
        """Split this rank's partial by key hash, send each peer its share
        as one packed block (accumulators cross the wire, never finalized
        means) and merge the shares that land here in rank order."""
        rec, rank = current(), self.tr.rank
        with rec.span("agg:split", cat="kernel"):
            split = partials[0].split_by_key_hash(self.P)
        for dst in range(self.P):
            if dst == rank:
                continue
            block = encode_agg_map(split[dst])
            if block is not None:
                count_shuffle(stats, block.nbytes)
            self.tr.send(dst, tag, block)
        shares = []
        for src in range(self.P):
            if src == rank:
                shares.append(split[rank])
            else:
                block = self.tr.recv(src, tag)
                if block is not None:
                    shares.append(decode_agg_map(block, spec))
        with rec.span("agg:merge", cat="kernel"):
            return [AggMap.merged(spec, shares)]

    def gather(self, parts, tag: str, stats: ExecStats, root: int = 0
               ) -> Optional[List[List[VectorList]]]:
        """Gather-merge: every rank ships its batches to ``root`` (a rank,
        or :data:`DRIVER`). Returns the per-source batch lists at the
        root, ``None`` elsewhere."""
        rank = self.tr.rank
        with exchange_span("gather", tag, stats):
            if rank != root:
                blocks = [encode_batch(vl) for vl in parts[0]]
                count_shuffle(stats, sum(b.nbytes for b in blocks))
                self.tr.send(root, tag, blocks)
                return None
            return [parts[0] if src == rank else
                    [decode_batch(b) for b in self.tr.recv(src, tag)]
                    for src in range(self.P)]
