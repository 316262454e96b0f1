"""The plain reference against the program's own numpy oracle
(LocalQueryRunner.execute_reference) at sf0.01 on the CPU, and the
reference's own copy of the population against the program's generator."""
import numpy as np
import pytest

import check
import sampler
from cells import Query

SF = 0.01
QUERIES = ["tpch/q1", "tpch/q6", "tpch/q12", "tpch/q14"]


@pytest.fixture(scope="module")
def reference():
    return check.Reference({n: Query(n) for n in QUERIES}, SF)


@pytest.fixture(scope="module")
def oracle():
    from presto_tpu.exec.runner import LocalQueryRunner
    return LocalQueryRunner(f"sf{SF:g}")


@pytest.mark.parametrize("name", QUERIES)
def test_reference_equals_the_programs_oracle(name, reference, oracle):
    q = Query(name)
    for i in range(3):
        values = sampler.draw(q.parameters, sampler.rng(25, name, i))
        want = oracle.execute_reference(sampler.inline(q, values)).rows
        got = reference.answer(name, values)
        assert [list(r) for r in got] == [list(r) for r in want], values


def test_population_copy_equals_the_programs_generator():
    from presto_tpu.connectors import tpch as H
    from reference import tpch_data as D
    gens = {"lineitem": H._gen_lineitem, "orders": H._gen_orders,
            "part": H._gen_part}
    wanted = {}
    for name in QUERIES:
        for table, cols in Query(name).tables.items():
            wanted.setdefault(table, set()).update(cols)
    for table, cols in wanted.items():
        mine = D.table(table, sorted(cols), 0.05)
        idx = np.arange(H.table_row_count(table, 0.05), dtype=np.int64)
        for c in cols:
            theirs = gens[table](c, idx, 0.05)
            theirs = theirs[0] if isinstance(theirs, tuple) else theirs
            assert np.array_equal(mine[c], np.asarray(theirs).astype(np.int64)), (table, c)
    assert D.DICTIONARIES[("lineitem", "shipmode")] == tuple(H.MODES)
    assert D.DICTIONARIES[("part", "type")] == tuple(H.TYPES)


def test_exact_grouped_sums_hold_where_float64_would_round():
    from reference.exact import grouped_sums
    big = np.full(1000, (1 << 40) + 1, np.int64)
    codes = np.arange(1000) % 2
    assert grouped_sums(codes, 2, big) == [500 * ((1 << 40) + 1)] * 2
    assert grouped_sums(codes, 2, big, approximate=True) != [500 * ((1 << 40) + 1)] * 2
    with pytest.raises(ValueError):
        grouped_sums(codes, 2, -big)
