"""The distributed worker runtime (paper §5's staged distributed plans,
made real): a driver plus N workers, each owning its own
:class:`~repro.objectmodel.store.PagedStore` shard and executing pipeline
stages locally through the one op layer
(:class:`~repro.core.executor.Executor`), connected by the transport
exchange (:class:`~repro.dist.exchange.TransportExchange`): hash-partition
shuffle (JOIN sides, AGG partials), broadcast (small-side joins), and
gather-merge (TOPK, SORT, ``collect()``).

Transfers are page-granular: the wire format *is* the page byte format
(:meth:`~repro.objectmodel.store.PagedSet.to_payloads` /
:meth:`~repro.objectmodel.store.PagedSet.from_payloads`), so neither end
parses anything. Workers run as threads, forked processes, or framed-TCP
socket peers (``worker_kind="socket"`` — true multi-host: launch workers
anywhere with ``python -m repro.dist.worker --connect host:port``) behind
a common transport interface.

Front door: ``Session(backend="workers", num_workers=N)``, or
:class:`~repro.dist.driver.DistributedExecutor` directly.
"""
from repro.dist.driver import DistributedExecutor
from repro.dist.exchange import SocketTransport, TransportExchange
from repro.dist.placement import build_shard_store, place_scans
from repro.dist.protocol import (DRIVER, PageBlock, PickleBlock,
                                 ProtocolError, decode_batch, decode_frame,
                                 encode_batch, frame_buffers, read_frame,
                                 write_frame)
from repro.dist.worker import WorkerRuntime, connect_worker, run_remote_worker

__all__ = [
    "DistributedExecutor", "WorkerRuntime", "DRIVER", "PageBlock",
    "PickleBlock", "ProtocolError", "encode_batch", "decode_batch",
    "frame_buffers", "write_frame", "read_frame", "decode_frame",
    "TransportExchange", "place_scans",
    "build_shard_store", "SocketTransport", "connect_worker",
    "run_remote_worker",
]
