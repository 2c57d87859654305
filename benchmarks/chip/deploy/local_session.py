"""A local ``Session``: the query runs in this process, over
``num_partitions`` partitions, with its fused stages and segment
reduction on the chip."""
from __future__ import annotations


class Deployment:
    def __init__(self, config: dict, schema, trace: bool):
        from repro.core import Session
        from repro.objectmodel.store import PagedStore
        self.schema = schema
        self.session = Session(store=PagedStore(),
                               num_partitions=config["partitions"],
                               expr_backend=config["expr_backend"],
                               trace=trace)
        self.sets = []

    def load(self, tables) -> None:
        for rec in tables:
            self.sets.append(
                self.session.load("lineitem", rec, self.schema).set_name)

    def client(self, tenant: int):
        """The session and set name that tenant's queries run on."""
        return self.session, self.sets[tenant]

    def tap_aggregates(self, sink: list) -> None:
        """From now on append each AGG op's output (a list of partitions,
        each a list of column batches) to ``sink``: the groups before any
        later filter, kept by reference for the check after the window."""
        ex = self.session.executor
        inner = ex._aggregate

        def tapped(*args, **kw):
            out = inner(*args, **kw)
            sink.append(out)
            return out
        ex._aggregate = tapped

    def close(self) -> None:
        self.session = None
