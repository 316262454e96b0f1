"""Connector catalog: registry + dispatch over connector modules.

The slim analog of the reference's connector SPI surface
(presto-spi/.../spi/connector/ConnectorMetadata.java:73 for table/column
metadata, ConnectorSplitManager.java:23 for splits): the engine layers
(planner, pipeline compiler, scheduler, reference interpreter) call this
module instead of a concrete connector.  Connector modules are duck-typed —
they expose SCHEMAS / PREFIXES / OPEN_DOMAIN / ROWID_* / table_row_count /
generate_column / generate_values_at / column_type (see tpch.py, tpcds.py).

Table names are resolved with a session-preferred connector first (the
reference's session catalog), then any other registered connector — the two
built-ins overlap only on `customer`.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

import numpy as np

from . import tpch as _tpch
from . import tpcds as _tpcds

_CONNECTORS = {"tpch": _tpch, "tpcds": _tpcds}

# merged (table, column) property sets; cross-connector collisions are
# impossible in practice (tpcds columns carry their table prefix)
OPEN_DOMAIN = set(_tpch.OPEN_DOMAIN) | set(_tpcds.OPEN_DOMAIN)
ROWID_ORDERED = set(_tpch.ROWID_ORDERED) | set(_tpcds.ROWID_ORDERED)
ROWID_DISTINCT = set(_tpch.ROWID_DISTINCT) | set(_tpcds.ROWID_DISTINCT)


@dataclass
class HostColumn:
    """Host-generated column carrying a null mask (storage connectors can
    produce NULLs; the generated tpch/tpcds columns never do).  `values` is
    a numpy array or a (codes, dictionary-values) tuple."""
    values: object
    nulls: Optional[np.ndarray] = None


def _rebuild_property_sets() -> None:
    """Recompute the merged per-column property sets from the registered
    connectors (mutated in place: engine code holds references)."""
    for merged, attr in ((OPEN_DOMAIN, "OPEN_DOMAIN"),
                         (ROWID_ORDERED, "ROWID_ORDERED"),
                         (ROWID_DISTINCT, "ROWID_DISTINCT")):
        merged.clear()
        for conn in _CONNECTORS.values():
            merged.update(getattr(conn, attr))


def register_connector(connector_id: str, connector) -> None:
    """Register a connector instance/module at runtime (the Plugin.java:42 /
    ConnectorFactory analog).  `connector` is duck-typed: see module doc."""
    _CONNECTORS[connector_id] = connector
    _rebuild_property_sets()


def unregister_connector(connector_id: str) -> None:
    _CONNECTORS.pop(connector_id, None)
    _rebuild_property_sets()


def module(connector_id: str):
    return _CONNECTORS[connector_id]


def resolve_table(name: str, preferred: str = "tpch") -> Optional[str]:
    """Table name -> connector id (session-preferred connector wins)."""
    order = [preferred] + [c for c in _CONNECTORS if c != preferred]
    for cid in order:
        if name in _CONNECTORS[cid].SCHEMAS:
            return cid
    return None


def _module_for_table(table: str):
    cid = resolve_table(table)
    if cid is None:
        raise KeyError(f"unknown table {table!r}")
    return _CONNECTORS[cid]


# ---------------------------------------------------------------------------
# dispatching mirrors of the connector API (by table name; the two built-in
# catalogs agree on `customer`'s generator module only via resolve order, so
# engine code that may see either passes the connector id explicitly where
# it has one — the lazy-column tag and TableHandle carry it)
# ---------------------------------------------------------------------------

def schema(table: str, connector_id: Optional[str] = None):
    m = _CONNECTORS[connector_id] if connector_id else _module_for_table(table)
    return m.SCHEMAS[table]

def prefix(table: str, connector_id: Optional[str] = None) -> str:
    m = _CONNECTORS[connector_id] if connector_id else _module_for_table(table)
    return m.PREFIXES[table]

def column_type(table: str, column: str, connector_id: Optional[str] = None):
    m = _CONNECTORS[connector_id] if connector_id else _module_for_table(table)
    return m.column_type(table, column)

def table_row_count(table: str, sf: float,
                    connector_id: Optional[str] = None) -> int:
    m = _CONNECTORS[connector_id] if connector_id else _module_for_table(table)
    return m.table_row_count(table, sf)

def generate_column(table: str, column: str, sf: float, start: int,
                    count: int, connector_id: Optional[str] = None):
    m = _CONNECTORS[connector_id] if connector_id else _module_for_table(table)
    return m.generate_column(table, column, sf, start, count)

def generate_values_at(table: str, column: str, sf: float, ids,
                       connector_id: Optional[str] = None) -> list:
    m = _CONNECTORS[connector_id] if connector_id else _module_for_table(table)
    return m.generate_values_at(table, column, sf, ids)


def generate_dictionary_at(table: str, column: str, sf: float, ids,
                           connector_id: Optional[str] = None):
    """(codes, values) where the connector can give a string column so
    (the generated catalogs' enumerated columns), else None: the caller
    falls back to `generate_values_at`."""
    m = _CONNECTORS[connector_id] if connector_id else _module_for_table(table)
    fn = getattr(m, "generate_dictionary_at", None)
    return None if fn is None else fn(table, column, sf, ids)


# ---------------------------------------------------------------------------
# splits (reference ConnectorSplitManager / TpchSplitManager)
# ---------------------------------------------------------------------------

@dataclass
class TableSplit:
    """A row-range shard of one generated table."""
    connector: str
    table: str
    sf: float
    start: int
    end: int

    def to_dict(self):
        return {"connectorId": self.connector, "table": self.table,
                "sf": self.sf, "start": self.start, "end": self.end}

    @staticmethod
    def from_dict(d):
        return TableSplit(d.get("connectorId", "tpch"), d["table"], d["sf"],
                          d["start"], d["end"])


def make_splits(table: str, sf: float, splits: int,
                connector_id: Optional[str] = None) -> List[TableSplit]:
    cid = connector_id or resolve_table(table)
    total = table_row_count(table, sf, cid)
    per = (total + splits - 1) // splits
    return [TableSplit(cid, table, sf, i * per, min((i + 1) * per, total))
            for i in range(splits) if i * per < total]


# ---------------------------------------------------------------------------
# bucketing metadata for grouped (lifespan) execution — the
# ConnectorMetadata bucketing surface the reference's
# GroupedExecutionTagger consults (see connectors/tpch.py BUCKET_COLUMNS)
# ---------------------------------------------------------------------------

def bucket_column(table: str,
                  connector_id: Optional[str] = None) -> Optional[str]:
    """The column this table is range-bucketed on, or None.

    Contract: a declared bucket column is NON-NULL.  Grouped execution
    assigns each output group to exactly one lifespan by its bucket-key
    value; a NULL key has no home bucket, so its group would be replayed
    (and its aggregate duplicated) across lifespans.  The engine
    re-checks this at eligibility time (exec/grouped.py rejects plans
    whose anchor key can be null), but a connector must never declare a
    nullable column here."""
    m = _CONNECTORS.get(connector_id) if connector_id \
        else _module_for_table(table)
    if m is None:
        return None
    return getattr(m, "BUCKET_COLUMNS", {}).get(table)


def bucket_layout(sf: float, n_buckets: int,
                  connector_id: Optional[str] = None):
    """Co-bucketed lifespan layout (list of TableBucket), or None when the
    connector has no bucketing.  Each TableBucket's key range
    [key_lo, key_hi) maps to the contiguous row range holding exactly
    those (non-null — see bucket_column) keys in every co-bucketed
    table; successive buckets tile both the key domain and each table's
    rows."""
    m = _CONNECTORS.get(connector_id)
    fn = getattr(m, "bucket_layout", None) if m is not None else None
    return None if fn is None else fn(sf, n_buckets)
