"""The worker runtime: one rank of the op layer, over one shard.

Every worker runs the *same* op sequence (the paper's staged plan) through
the one op layer, :class:`~repro.core.executor.Executor`, over the one
partition it holds: its own :class:`~repro.objectmodel.store.PagedStore`
shard. :class:`WorkerRuntime` binds a
:class:`~repro.dist.exchange.TransportExchange` in place of the local
executor's in-memory exchange, so at the ops the physical plan stages
across workers its rows cross to the peers as page blocks: the JOIN
sides' shuffle or the build side's broadcast, the AGG partials, the
TOPK and SORT candidates gathered to rank 0. It overrides only the scan
(of its shard) and OUTPUT (a gather to the driver).

Because placement is the same greedy-by-bytes rule the local executor
uses and exchanges preserve (source rank, batch) order, results are
byte-identical to ``Executor`` with ``num_partitions == num_workers`` —
enforced by ``tests/test_dist.py``.
"""
from __future__ import annotations

import socket
import time
import traceback
from typing import Dict, List, Optional, Tuple

from repro.core.executor import Executor
from repro.core.physical import PhysicalPlan, plan_from_wire
from repro.core.tcap import TCAPOp, TCAPProgram
from repro.dist.exchange import (PeerAborted, SocketTransport,
                                 TransportExchange)
from repro.dist.protocol import (DRIVER, HELLO, PROTO_VERSION, SETUP,
                                 WELCOME, ProtocolError, StatsFrame,
                                 configure_socket, read_frame, write_frame)
from repro.obs.trace import NULL, SpanRecorder, using
from repro.objectmodel.store import PagedSet, PagedStore
from repro.objectmodel.vectorlist import VectorList

__all__ = ["WorkerRuntime", "worker_main", "connect_worker",
           "build_setup_shard", "run_remote_worker", "main"]


class WorkerRuntime(Executor):
    """One worker: a rank, its shard store, and a transport to its peers.
    The program arrives optimized and planned, so it runs as shipped."""

    def __init__(self, rank: int, num_workers: int, transport,
                 shard: PagedStore, vector_rows: int = 8192,
                 expr_backend: str = "numpy"):
        super().__init__(shard, num_workers, vector_rows, do_optimize=False,
                         expr_backend=expr_backend)
        self.rank = rank
        self.tr = transport
        self.exchange = TransportExchange(transport, num_workers)

    def _scan(self, op: TCAPOp) -> List[List[VectorList]]:
        s = self.store.get_set(op.info["set"])
        col = op.out_cols[0]
        batches: List[VectorList] = []
        for page_records in s.scan():
            self.stats.pages_scanned += 1
            self.stats.rows_scanned += len(page_records)
            for j in range(0, len(page_records), self.vector_rows):
                batches.append(
                    VectorList({col: page_records[j: j + self.vector_rows]}))
        return [batches]

    def _output(self, op: TCAPOp, i: int, parts) -> None:
        out = [vl.project(op.apply_cols) for vl in parts[0]]
        self.stats.rows_output = sum(vl.num_rows or 0 for vl in out)
        self.exchange.gather([out], f"{i}:output", self.stats, root=DRIVER)


def worker_main(rank: int, num_workers: int, transport, shard: PagedStore,
                vector_rows: int, prog: TCAPProgram,
                plan: PhysicalPlan, expr_backend: str = "numpy",
                trace: bool = False, runtime_cls=None) -> bool:
    """Entry point for every worker kind: run, then report stats (or the
    failure) to the driver. With ``trace=True`` the worker records its own
    rank-attributed spans and ships them back inside the ``done`` stats
    frame. ``runtime_cls`` swaps the runtime (the service's resident
    worker injects its write-materializing subclass). Returns whether the
    query completed here — False when it aborted (a peer failed) or this
    worker errored, so process-worker entry points can exit nonzero for
    supervisors.

    The worker compiles its own stage plan from the shipped program —
    compilation is deterministic and the kernel LRU is process-wide, so
    thread workers share one jitted kernel per query shape and fork
    workers rebuild identical ones (prefer ``worker_kind="thread"`` with
    ``expr_backend="jax"``: XLA's runtime threads do not survive a fork
    taken after jax initialized in the parent)."""
    rec = SpanRecorder(rank=rank) if trace else NULL
    try:
        rt = (runtime_cls or WorkerRuntime)(rank, num_workers, transport,
                                            shard, vector_rows, expr_backend)
        with using(rec):
            with rec.span("worker", cat="phase", rank=rank):
                rt.execute_program(prog, plan, trace=rec)
        transport.send(DRIVER, "done",
                       StatsFrame(rt.stats, list(rec.spans)))
        return True
    except PeerAborted:
        return False  # the driver raised already; nothing left to report
    except BaseException:
        try:
            transport.send(DRIVER, "error", traceback.format_exc())
        except Exception:
            pass  # transport already dead; the driver's pump reports it
        return False


# ----------------------------------------------------- socket rendezvous
def connect_worker(addr: Tuple[str, int], *, rank: Optional[int] = None,
                   epoch: Optional[str] = None, timeout: float = 30.0,
                   retry_seconds: float = 0.0,
                   hello_extra: Optional[Dict] = None):
    """Dial the driver's rendezvous at ``addr`` and handshake: send HELLO
    (protocol version + the launched worker's pre-assigned rank/epoch, or
    ``None`` for an external worker asking to be assigned one), expect
    WELCOME back. Returns ``(socket, welcome)`` with the socket blocking
    and Nagle disabled (exchange frames are latency-sensitive). With
    ``retry_seconds``, the initial TCP connect is retried until the window
    closes — external workers may be started before the driver listens.
    ``hello_extra`` rides along in the HELLO payload — ``--serve`` workers
    announce the shards they retained (``held``/``prev``) through it."""
    deadline = time.monotonic() + retry_seconds
    while True:
        try:
            sock = socket.create_connection(addr, timeout=timeout)
            break
        except OSError:
            if time.monotonic() >= deadline:
                raise
            time.sleep(0.2)
    try:
        configure_socket(sock)
        hello = {"proto": PROTO_VERSION, "rank": rank, "epoch": epoch}
        if hello_extra:
            hello.update(hello_extra)
        write_frame(sock, rank if rank is not None else DRIVER, DRIVER,
                    HELLO, hello)
        frame = read_frame(sock)
        if frame is None:
            raise ProtocolError(
                "driver closed the connection during handshake (stale "
                "epoch, duplicate rank, or a full rendezvous?)")
        _, _, tag, welcome = frame
        if tag != WELCOME or not isinstance(welcome, dict):
            raise ProtocolError(f"expected {WELCOME!r}, got {tag!r}")
        sock.settimeout(None)
        return sock, welcome
    except BaseException:
        sock.close()
        raise


def build_setup_shard(setup_sets: Dict,
                      retained: Optional[Dict[str, Tuple[int, PagedSet]]]
                      = None) -> PagedStore:
    """Materialize one SETUP frame's ``sets`` into a shard store. Entries
    are tagged (protocol v2): ``("pages", page_size, dtype, block, ver)``
    adopts shipped page bytes verbatim; ``("held", ver)`` reuses the
    retained :class:`PagedSet` from a previous connection at that version
    (the driver only emits it after the HELLO announced we hold it).
    With ``retained`` given, freshly shipped shards are recorded in it so
    the next reconnect can announce them."""
    shard = PagedStore()
    for name, entry in setup_sets.items():
        if entry[0] == "held":
            if retained is None or name not in retained:
                raise ProtocolError(
                    f"driver sent a 'held' reference for {name!r} but this "
                    "worker retains no such shard")
            ver, s = retained[name]
            if ver != entry[1]:
                raise ProtocolError(
                    f"'held' reference for {name!r} at version {entry[1]} "
                    f"but the retained shard is version {ver}")
            shard.sets[name] = s
        else:
            _, page_size, dtype, block, ver = entry
            s = PagedSet.from_payloads(name, dtype, block.payloads,
                                       page_size)
            shard.sets[name] = s
            if retained is not None:
                retained[name] = (ver, s)
    return shard


def run_remote_worker(addr: Tuple[str, int], serve: bool = False,
                      retry_seconds: float = 30.0) -> Tuple[int, int]:
    """A worker on (potentially) another machine: connect to the driver's
    advertised ``host:port``, receive rank + the query setup (program,
    physical plan, this rank's shard pages — page bytes adopted verbatim),
    run it, report. One query per connection; with ``serve=True`` the
    worker reconnects for subsequent queries until the driver goes away —
    *retaining* its shard pages between connections and announcing them
    (set name → version, plus the rank/P they were placed for) in the
    HELLO, so a warm reconnect gets a ``("held", version)`` manifest
    reference instead of the page bytes.

    When the WELCOME says the far end is a persistent
    :class:`~repro.service.service.QueryService` (``welcome["service"]``),
    the connection is handed to the resident loop — many queries share
    one connection, multiplexed by query id — and its counts are merged.

    Returns ``(completed, failed)`` query counts — failed covers queries
    that aborted (a peer died) or errored here, so the entry point can
    exit nonzero for supervisors."""
    queries = 0
    failed = 0
    retained: Dict[str, Tuple[int, PagedSet]] = {}
    prev: Optional[Dict] = None  # {"rank": r, "P": P} from the last query
    # set after each served query: between queries the driver's
    # per-query listener flaps, so a redial can be refused or cut
    # mid-handshake (the dying listener's backlog is reset) just as the
    # next query's listener opens — those must be retried, bounded by
    # one retry window per gap. On the *first* dial (deadline unset) a
    # refusal or drop is a verdict (driver absent / rendezvous full).
    redial_deadline: Optional[float] = None
    while True:
        extra = ({"held": {n: v for n, (v, _) in retained.items()},
                  "prev": prev} if serve and prev is not None else None)
        window = (retry_seconds if redial_deadline is None
                  else redial_deadline - time.monotonic())
        if window <= 0:
            return queries, failed  # driver stayed gone; done serving
        try:
            sock, welcome = connect_worker(addr, retry_seconds=window,
                                           hello_extra=extra)
        except (OSError, ProtocolError):
            if redial_deadline is None:
                raise
            time.sleep(0.2)
            continue
        redial_deadline = None
        rank, P = int(welcome["rank"]), int(welcome["P"])
        if welcome.get("service"):
            from repro.service.resident import serve_resident
            q, f = serve_resident(sock, welcome)
            queries += q
            failed += f
            if not serve:
                return queries, failed
            prev = None
            retained.clear()
            redial_deadline = time.monotonic() + retry_seconds
            continue
        frame = read_frame(sock)
        if frame is None:
            sock.close()
            raise ProtocolError("driver closed before shipping the query "
                                "setup")
        _, _, tag, setup = frame
        if tag != SETUP:
            sock.close()
            raise ProtocolError(f"expected {SETUP!r}, got {tag!r}")
        prog = setup["prog"]
        plan = plan_from_wire(prog, setup["plan"])
        if not serve:
            shard = build_setup_shard(setup["sets"])
        else:
            if prev is not None and (rank, P) != (prev["rank"], prev["P"]):
                # assigned a different rank (or the pool was resized) —
                # the retained shards are the wrong partition now; drop
                # them (the driver knows: on a rank/P mismatch it never
                # honors ``held`` and ships pages)
                retained.clear()
            shard = build_setup_shard(setup["sets"], retained)
        prev = {"rank": rank, "P": P}
        tr = SocketTransport(rank, sock)
        ok = worker_main(rank, P, tr, shard, setup["vector_rows"], prog,
                         plan, setup["expr_backend"],
                         trace=bool(setup.get("trace", False)))
        tr.close()
        if ok:
            queries += 1
        else:
            failed += 1
        if not serve:
            return queries, failed
        redial_deadline = time.monotonic() + retry_seconds


def main(argv=None) -> int:
    """``python -m repro.dist.worker --connect host:port`` — launch one
    worker process that joins a ``Session(backend="workers",
    worker_kind="socket", socket_launch="connect", ...)`` driver."""
    import argparse
    import sys
    ap = argparse.ArgumentParser(
        prog="python -m repro.dist.worker",
        description="Join a PlinyCompute socket-transport driver as one "
                    "worker (true multi-host: run this on any machine "
                    "that can reach the driver's advertised host:port).")
    ap.add_argument("--connect", required=True, metavar="HOST:PORT",
                    help="the driver's rendezvous address")
    ap.add_argument("--serve", action="store_true",
                    help="reconnect and serve subsequent queries until "
                         "the driver goes away (default: one query)")
    ap.add_argument("--retry-seconds", type=float, default=30.0,
                    help="keep retrying the initial connect this long "
                         "(the worker may be started before the driver)")
    args = ap.parse_args(argv)
    host, _, port = args.connect.rpartition(":")
    if not host or not port.isdigit():
        ap.error(f"--connect takes HOST:PORT, got {args.connect!r}")
    try:
        served, failed = run_remote_worker((host, int(port)),
                                           serve=args.serve,
                                           retry_seconds=args.retry_seconds)
    except (OSError, ProtocolError) as e:
        # e.g. driver unreachable, or accepted-then-dropped (rendezvous
        # already full: more workers dialed than num_workers)
        print(f"worker: could not join the driver at {args.connect}: {e}",
              file=sys.stderr)
        return 1
    print(f"worker: served {served} "
          f"quer{'y' if served == 1 else 'ies'}"
          + (f", {failed} aborted/failed" if failed else ""),
          file=sys.stderr)
    # nonzero when any query did not complete here (peer death or own
    # error) so a supervisor keyed on the exit code can react
    return 1 if failed else 0


if __name__ == "__main__":  # pragma: no cover - exercised via subprocess
    import sys
    sys.exit(main())
