"""A local ``Session`` over several tables: the query runs in this
process, over ``num_partitions`` partitions, with its fused stages and
segment reduction on the chip. Each tenant's tables are loaded as sets of
their own, typed by a schema named after the table."""
from __future__ import annotations

from deploy import local_session


def _schema(dtype, name: str):
    from repro.objectmodel.schema import Field, record
    return record(name, {f: Field(dtype.fields[f][0]) for f in dtype.names})


class Deployment(local_session.Deployment):
    def load(self, tables) -> None:
        """Load each tenant's tables (``tables()`` of the generator's
        object); lineitem takes the schema the harness made of it."""
        for tenant in tables:
            sets = {}
            for name, rec in tenant.tables().items():
                schema = (self.schema if rec.dtype == self.schema.dtype
                          else _schema(rec.dtype, f"Tpch{name.title()}"))
                sets[name] = (self.session.load(name, rec, schema).set_name,
                              schema)
            # client() hands a template each table's set name and schema
            self.sets.append(sets)
