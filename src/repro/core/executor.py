"""PC's vectorized execution engine (paper §5.2, Appendix C/D), host side.

Pipelines push *vector lists* (column batches) through compiled stages.
:class:`Executor` is the one op layer of every backend: one dispatch loop
and one implementation of each op, run over the partitions this process
holds (all P of them here, one per rank on a distributed worker):

* **JOIN** — broadcast (build side replicated) or hash-partition (both sides
  shuffled by key hash) per the physical planner's decision, then build+probe;
* **AGG** — PC's two-stage plan: per-partition *pre-aggregation* into maps
  ("combiner pages"), shuffle partials by key hash, final aggregation;
* **TOPK** — per-partition top-k, then a global merge at partition 0 (the
  paper's TopJaccard pattern);
* **SORT** — ``ORDER BY ... LIMIT``: per-partition pre-selection, then a
  global gather-merge at partition 0.

The ops reach other partitions only through an *exchange* with four
operations: a join side's hash shuffle, a build side's broadcast, the AGG
partials' split/move/merge, and a gather to partition 0. Two exchanges
exist, and they are all that differs between the backends:
:class:`LocalExchange` here moves buckets in memory between the P
partitions simulated in one process; the distributed worker runtime
(:class:`repro.dist.worker.WorkerRuntime`, a subclass that overrides only
the scan of its shard and the OUTPUT gather) binds
:class:`repro.dist.exchange.TransportExchange`, which moves page blocks
between ranks. The per-partition kernels live in :mod:`repro.core.relops`;
placement is greedy least-loaded pages, shared with ``dist.placement``.

A row-at-a-time *volcano* interpreter (:class:`NaiveExecutor`) implements
identical semantics one record at a time — the execution model the paper
argues is obsolete — and serves as the measured baseline for the
paper-claims validation benchmarks.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

import numpy as np

from repro.core.compiler import compile_graph
from repro.core.computations import Computation
from repro.core.exprc import EXPR_BACKENDS, FusedStage, build_steps
from repro.core.optimizer import OptimizerReport, optimize
from repro.core.physical import PhysicalPlan, plan_physical
from repro.core.relops import (AggMap, AggSpec, assemble_output,
                               batch_kernel, batch_topk, bucket_by_hash,
                               bytes_of, concat_batches, count_shuffle,
                               device_segment_reducer, exchange_span,
                               greedy_page_placement, merge_ordered,
                               merge_topk, order_partition, probe_join)
from repro.core.tcap import TCAPOp, TCAPProgram
from repro.obs.trace import NULL, current, op_name, using
from repro.objectmodel.store import PagedStore
from repro.objectmodel.vectorlist import VectorList

__all__ = ["Executor", "NaiveExecutor", "ExecStats", "LocalExchange"]


@dataclasses.dataclass
class ExecStats:
    pages_scanned: int = 0
    rows_scanned: int = 0
    rows_joined: int = 0
    rows_output: int = 0
    shuffle_bytes: int = 0
    broadcast_joins: int = 0
    hash_partition_joins: int = 0
    exchanges_elided: int = 0
    optimizer: Optional[OptimizerReport] = None


def _part_rows(parts) -> int:
    """Total rows across a partitioned batch list (trace attribute only —
    called solely when a recorder is enabled)."""
    return sum(vl.num_rows or 0 for batches in parts for vl in batches)


class LocalExchange:
    """The exchange between the P partitions of one process: rows move as
    buckets in memory. Each operation takes all P partitions and returns
    one result per partition. Its byte accounting: a join shuffle counts
    the rows that change partition, a broadcast P-1 copies of the build
    side's raw column bytes, the AGG exchange every share, the
    partition's own included."""

    def shuffle(self, parts, hash_name: str, tag: str,
                stats: ExecStats) -> List[VectorList]:
        """Repartition batches by ``hash % P`` (the network shuffle)."""
        P = len(parts)
        with exchange_span("shuffle", tag, stats):
            buckets: List[List[VectorList]] = [[] for _ in range(P)]
            for pi, batches in enumerate(parts):
                for p, subs in enumerate(
                        bucket_by_hash(batches, hash_name, P)):
                    if p != pi:
                        for sub in subs:
                            count_shuffle(stats, bytes_of(sub))
                    buckets[p] += subs
            return [concat_batches(b) for b in buckets]

    def broadcast(self, parts, tag: str,
                  stats: ExecStats) -> List[VectorList]:
        """The whole build side, for every partition."""
        with exchange_span("bcast", tag, stats):
            build = concat_batches([vl for bl in parts for vl in bl])
            count_shuffle(stats, bytes_of(build) * max(0, len(parts) - 1))
        return [build] * len(parts)

    def merge_partials(self, spec: AggSpec, partials: List[AggMap],
                       tag: str, stats: ExecStats) -> List[AggMap]:
        """Split every partial by key hash; each final merges its share of
        every partial, in partition order."""
        P = len(partials)
        rec = current()
        with exchange_span("shuffle", tag, stats):
            splits = []
            for m in partials:
                with rec.span("agg:split", cat="kernel"):
                    splits.append(m.split_by_key_hash(P))
            finals = []
            for p in range(P):
                with rec.span("agg:merge", cat="kernel"):
                    shares = [split[p] for split in splits if split[p]]
                    for share in shares:
                        count_shuffle(stats, share.nbytes())
                    finals.append(AggMap.merged(spec, shares))
        return finals

    def gather(self, parts, tag: str, stats: ExecStats):
        """Every partition's batches, at partition 0: nothing moves."""
        return parts


class Executor:
    """Vectorized TCAP executor over a PagedStore with P logical partitions."""

    #: stage-compiler backend; NaiveExecutor pins "interp" (see below)
    expr_backend = "numpy"
    #: how rows reach other partitions (a worker rank binds its transport)
    exchange = LocalExchange()

    def __init__(self, store: PagedStore, num_partitions: int = 4,
                 vector_rows: int = 8192, do_optimize: bool = True,
                 broadcast_threshold_bytes: int = 2 << 30,
                 write_outputs: bool = True,
                 expr_backend: Optional[str] = None):
        self.store = store
        self.P = num_partitions
        self.vector_rows = vector_rows
        self.do_optimize = do_optimize
        self.broadcast_threshold = broadcast_threshold_bytes
        # when False, OUTPUT never writes back to the store — the caller
        # (the Session facade) materializes results itself so single- and
        # multi-column outputs get the same structured-record treatment.
        self.write_outputs = write_outputs
        if expr_backend is not None:
            if expr_backend not in EXPR_BACKENDS:
                raise ValueError(f"unknown expr_backend {expr_backend!r} "
                                 f"(expected one of {EXPR_BACKENDS})")
            self.expr_backend = expr_backend
        self.stats = ExecStats()

    # ------------------------------------------------------------ public
    def execute(self, sink: Computation) -> Dict[str, np.ndarray]:
        prog = compile_graph(sink)
        return self.execute_program(prog)

    def execute_program(self, prog: TCAPProgram,
                        plan: Optional[PhysicalPlan] = None,
                        steps: Optional[list] = None,
                        trace=None) -> Dict[str, np.ndarray]:
        """Run a TCAP program. ``plan`` / ``steps`` let the Session front-end
        pass its cached physical plan and compiled stage plan; standalone
        callers leave them None and both are derived here. ``trace`` is a
        :class:`~repro.obs.trace.SpanRecorder` to record per-op spans into
        (None — the default — records nothing)."""
        self.stats = ExecStats()
        if self.do_optimize:
            prog, rep = optimize(prog)
            self.stats.optimizer = rep
            plan = steps = None  # derived for the pre-optimized program
        if plan is None:
            plan = plan_physical(prog, self.store, self.broadcast_threshold,
                                 num_partitions=self.P)
        if steps is None:
            steps = build_steps(prog, self.expr_backend)
        return self._run(steps, plan, NULL if trace is None else trace)

    # --------------------------------------------------------- internals
    def _run(self, steps: list, plan: PhysicalPlan, rec=NULL
             ) -> Dict[str, np.ndarray]:
        # data[list_name][partition] -> list of VectorList batches
        data: Dict[str, List[List[VectorList]]] = {}
        result: Dict[str, np.ndarray] = {}

        # the op index within the program: exchange tags key on it (fused
        # steps advance it by their op count)
        i = -1
        with using(rec):
            for step in steps:
                if isinstance(step, FusedStage):
                    first, i = i + 1, i + len(step.ops)
                    name = op_name(first, i, [o.op for o in step.ops])
                    with rec.span(name, cat="op", idx=first) as sp:
                        data[step.out] = self._map_batches(
                            data[step.in_list], step)
                    if rec.enabled:
                        sp.set(rows=_part_rows(data[step.out]))
                    continue
                op = step
                i += 1
                sb0 = self.stats.shuffle_bytes
                with rec.span(op_name(i, i, [op.op]), cat="op",
                              idx=i, op=op.op) as sp:
                    if op.op == "SCAN":
                        data[op.out] = self._scan(op)
                    elif op.op in ("APPLY", "FILTER", "FLATTEN", "HASH"):
                        data[op.out] = self._map_batches(data[op.in_list],
                                                         batch_kernel(op))
                    elif op.op == "JOIN":
                        data[op.out] = self._join(
                            op, i, data[op.in_list], data[op.in_list2],
                            plan.join_algo.get(id(op), "hash_partition"),
                            elide=plan.join_elide.get(id(op), ()))
                    elif op.op == "AGG":
                        data[op.out] = self._aggregate(
                            op, i, data[op.in_list],
                            elide=id(op) in plan.agg_elide)
                    elif op.op == "TOPK":
                        data[op.out] = self._topk(op, i, data[op.in_list])
                    elif op.op == "SORT":
                        data[op.out] = self._order(op, i, data[op.in_list])
                    elif op.op == "OUTPUT":
                        result = self._output(op, i, data[op.in_list])
                    else:
                        raise ValueError(f"unknown op {op.op}")
                if rec.enabled:
                    sp.set(rows=(self.stats.rows_output
                                 if op.op == "OUTPUT"
                                 else _part_rows(data[op.out])),
                           bytes=self.stats.shuffle_bytes - sb0)
        return result

    def _scan(self, op: TCAPOp) -> List[List[VectorList]]:
        s = self.store.get_set(op.info["set"])
        parts: List[List[VectorList]] = [[] for _ in range(self.P)]
        col = op.out_cols[0]
        # skew-aware placement, identical to the distributed runtime's
        # (dist.placement shares this helper): least-loaded-by-bytes,
        # degenerating to round-robin for equal-size pages
        dest = greedy_page_placement(
            [c * s.dtype.itemsize for c in s.counts], self.P)
        for i, page_records in enumerate(s.scan()):
            self.stats.pages_scanned += 1
            self.stats.rows_scanned += len(page_records)
            for j in range(0, len(page_records), self.vector_rows):
                batch = page_records[j: j + self.vector_rows]
                parts[dest[i]].append(VectorList({col: batch}))
        if not any(parts):
            # an empty set still flows one zero-row batch through the
            # stages, so the output columns carry their computed dtypes
            parts[0].append(VectorList({col: np.zeros(0, s.dtype)}))
        return parts

    def _map_batches(self, parts, fn) -> List[List[VectorList]]:
        return [[fn(vl) for vl in batches] for batches in parts]

    # ------------------------------------------------------------- join
    def _join(self, op: TCAPOp, i: int, left, right, algo: str,
              elide: Tuple[str, ...] = ()) -> List[List[VectorList]]:
        """``elide`` names the hash-join sides ("L"/"R") the plan proved
        already hash-partitioned on their join key (PL202): those concat
        in place — byte-identical to shuffling, since the shuffle of a
        correctly-placed side is the identity permutation — and count as
        elided exchanges instead of shuffle bytes. Every rank takes the
        same branch (the plan ships to the workers), so none waits on a
        peer that skipped the exchange."""
        stats = self.stats
        if algo == "broadcast":
            stats.broadcast_joins += 1
            rparts = self.exchange.broadcast(right, f"{i}:build", stats)
            lparts = [concat_batches(p) for p in left]
        else:
            stats.hash_partition_joins += 1
            sides = []
            for side, parts, col in (("L", left, op.apply_cols[0]),
                                     ("R", right, op.apply_cols2[0])):
                if side in elide:
                    stats.exchanges_elided += 1
                    sides.append([concat_batches(p) for p in parts])
                else:
                    sides.append(self.exchange.shuffle(
                        parts, col, f"{i}:{side}", stats))
            lparts, rparts = sides
        out: List[List[VectorList]] = [[] for _ in lparts]
        for p, (lvl, rvl) in enumerate(zip(lparts, rparts)):
            probed = probe_join(op, lvl, rvl)
            if probed is not None:
                res, n = probed
                stats.rows_joined += n
                out[p].append(res)
        return out

    # -------------------------------------------------------------- agg
    def _aggregate(self, op: TCAPOp, i: int, parts,
                   elide: bool = False) -> List[List[VectorList]]:
        spec = AggSpec.from_op(op)
        kcols, acols = spec.key_cols(op), spec.acc_cols(op)
        # the jax backend pre-aggregates on device: one fused segment-
        # reduce kernel per batch over all accumulator columns
        reducer = (device_segment_reducer(spec.combiners)
                   if self.expr_backend == "jax" else None)
        # stage 1: per-partition pre-aggregation (combiner pages), one
        # absorb over the partition's concatenated rows, which fixes the
        # float association order on every backend
        partials = []
        for batches in parts:
            m = AggMap(spec)
            m.absorb_batches(batches, kcols, acols, reducer=reducer)
            partials.append(m)
        # shuffle partials by key hash, final merge + finalize per partition;
        # when the planner proved the input already stable_key_hash-
        # partitioned on the key tuple, every partial holds only keys
        # routing to itself — the split+merge is the identity permutation,
        # so the partials *are* the finals and no bytes move
        if elide:
            self.stats.exchanges_elided += 1
            finals = partials
        else:
            finals = self.exchange.merge_partials(
                spec, partials, f"{i}:partials", self.stats)
        rec = current()
        out: List[List[VectorList]] = [[] for _ in finals]
        for p, m in enumerate(finals):
            with rec.span("agg:emit", cat="kernel"):
                emitted = m.emit()
            if emitted is not None:
                out[p].append(emitted)
        return out

    # ---------------------------------------------------- top-k and sort
    def _topk(self, op: TCAPOp, i: int, parts) -> List[List[VectorList]]:
        local = []
        for batches in parts:  # per-partition top-k, then merge
            best = [batch_topk(op, vl) for vl in batches]
            local.append([VectorList(
                {"score": np.concatenate([s for s, _ in best]),
                 "payload": np.concatenate([p for _, p in best])})]
                if best else [])
        return self._merge_at_root(
            parts, local, f"{i}:topk", lambda cands: merge_topk(
                op, [np.asarray(vl["score"]) for vl in cands],
                [np.asarray(vl["payload"]) for vl in cands]))

    def _order(self, op: TCAPOp, i: int, parts) -> List[List[VectorList]]:
        col = op.apply_cols[0]
        local = []
        for batches in parts:
            sel = order_partition(op, batches)
            local.append([VectorList({col: sel})] if sel is not None else [])
        return self._merge_at_root(
            parts, local, f"{i}:sort", lambda cands: merge_ordered(
                op, [np.asarray(vl[col]) for vl in cands]))

    def _merge_at_root(self, parts, local, tag: str, merge
                       ) -> List[List[VectorList]]:
        """Gather every partition's candidates to partition 0 and ``merge``
        them there, in partition-then-batch order."""
        gathered = self.exchange.gather(local, tag, self.stats)
        out: List[List[VectorList]] = [[] for _ in parts]
        if gathered is not None:
            merged = merge([vl for src in gathered for vl in src])
            if merged is not None:
                out[0].append(merged)
        return out

    def _output(self, op: TCAPOp, i: int, parts) -> Dict[str, np.ndarray]:
        return assemble_output(
            op, [vl for batches in parts for vl in batches],
            self.stats, self.store, self.write_outputs)


class NaiveExecutor(Executor):
    """Volcano-style record-at-a-time interpreter (paper §5.1's strawman).

    Identical semantics, but every stage is applied one record at a time via
    Python-level iteration — the cost model of a managed-runtime row
    iterator. Used only as the measured baseline in benchmarks. Always runs
    the per-op interpreter (``expr_backend="interp"``): fused stages would
    defeat the point of the strawman."""

    expr_backend = "interp"

    def __init__(self, *args, **kw):
        kw.pop("expr_backend", None)
        super().__init__(*args, **kw)
        self.expr_backend = "interp"

    def _map_batches(self, parts, fn) -> List[List[VectorList]]:
        out: List[List[VectorList]] = []
        for batches in parts:
            res = []
            for vl in batches:
                rows = []
                n = vl.num_rows or 0
                for i in range(n):  # row-at-a-time
                    row = VectorList({c: np.asarray(vl[c])[i:i + 1]
                                      for c in vl.names})
                    rows.append(fn(row))
                if rows:
                    acc = rows[0]
                    for r in rows[1:]:
                        acc = acc.concat(r)
                    res.append(acc)
                elif n == 0:
                    res.append(fn(vl))
            out.append(res)
        return out
