"""Plain numpy answers to the queries of the `tpchx` suite (TPC-H queries
over the tables of `reference/tpchx_data.py`), one module a query."""
