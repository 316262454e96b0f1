"""Verifier tests (reference presto-verifier AbstractVerification.java:74 +
checksum/): checksum-based A/B comparison between engines."""
from presto_tpu.exec.pipeline import ExecutionConfig
from presto_tpu.exec.runner import DistributedQueryRunner, LocalQueryRunner
from presto_tpu.verifier import (CONTROL_ERROR, MATCH, MISMATCH, TEST_ERROR,
                                 checksum_result, verify)

QUERIES = [
    "select count(*), sum(l_quantity) from lineitem",
    "select o_orderstatus, count(*) from orders group by o_orderstatus",
    "select n_name, r_name from nation join region "
    "on n_regionkey = r_regionkey",
    "select c_custkey, avg(o_totalprice) from customer "
    "left join orders on c_custkey = o_custkey group by c_custkey",
]


def test_engine_vs_reference_matches():
    r = LocalQueryRunner("sf0.01")
    results = verify(r.execute_reference, r.execute, QUERIES)
    assert [v.status for v in results] == [MATCH] * len(QUERIES)


def test_local_vs_distributed_matches():
    local = LocalQueryRunner("sf0.01")
    dist = DistributedQueryRunner("sf0.01", n_tasks=3, join_max_broadcast_table_size=0)
    results = verify(local.execute, dist.execute, QUERIES[:2])
    assert [v.status for v in results] == [MATCH, MATCH]


def test_spill_config_vs_default_matches():
    a = LocalQueryRunner("sf0.01")
    b = LocalQueryRunner("sf0.01", config=ExecutionConfig(
        batch_rows=1 << 14, join_out_capacity=1 << 16,
        memory_budget_bytes=200_000, spill_partitions=4))
    results = verify(a.execute, b.execute, QUERIES)
    assert [v.status for v in results] == [MATCH] * len(QUERIES)


def test_mismatch_detected():
    r = LocalQueryRunner("sf0.01")
    results = verify(
        lambda s: r.execute("select 1 k from region"),
        lambda s: r.execute("select 2 k from region"),
        ["q"])
    assert results[0].status == MISMATCH
    assert "k" in results[0].detail


def test_errors_classified():
    r = LocalQueryRunner("sf0.01")
    bad = "select * from no_such_table"
    good = "select count(*) from region"
    assert verify(r.execute, r.execute, [bad])[0].status == CONTROL_ERROR
    results = verify(lambda s: r.execute(good),
                     lambda s: r.execute(bad), ["q"])
    assert results[0].status == TEST_ERROR


def test_float_tolerance():
    r = LocalQueryRunner("sf0.01")
    a = r.execute("select avg(c_acctbal) from customer")
    b = r.execute_reference("select avg(c_acctbal) from customer")
    ca, cb = checksum_result(a), checksum_result(b)
    assert ca[0].matches(cb[0], rel_tol=1e-9)


def test_duplicate_column_names_not_collapsed():
    r = LocalQueryRunner("sf0.01")
    results = verify(
        lambda s: r.execute("select 1 a, 2 a from region"),
        lambda s: r.execute("select 1 a, 3 a from region"),
        ["q"])
    assert results[0].status == MISMATCH
