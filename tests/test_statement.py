"""Statement protocol (/v1/statement), dispatch queueing + resource groups,
StatementClient, and the CLI formatter — the client-layer analog of the
reference's QueuedStatementResource/ExecutingStatementResource +
StatementClientV1 + presto-cli (SURVEY.md §2.4, §2.11, L6)."""
import threading
import time

import pytest

from presto_tpu.cli import format_table, run_statement
from presto_tpu.client import QueryError, StatementClient
from presto_tpu.exec.pipeline import ExecutionConfig
from presto_tpu.worker import WorkerServer
from presto_tpu.worker.statement import (DispatchManager, FAILED, FINISHED,
                                         QUEUED, ResourceGroupManager,
                                         ResourceGroupSpec, RUNNING,
                                         Selector)


@pytest.fixture(scope="module")
def coordinator():
    server = WorkerServer(coordinator=True, environment="test",
                          config=ExecutionConfig(batch_rows=1 << 13))
    yield server
    server.close()


@pytest.fixture(scope="module")
def client(coordinator):
    return StatementClient(coordinator.uri, schema="sf0.01")


def test_select_round_trip(client):
    r = client.execute("SELECT returnflag, count(*) c FROM lineitem "
                       "GROUP BY returnflag ORDER BY returnflag")
    assert r.column_names == ["returnflag", "c"]
    assert len(r.rows) == 3
    assert r.stats["state"] == "FINISHED"


def test_decimal_and_null_decode(client):
    r = client.execute("SELECT sum(extendedprice*discount) rev, "
                       "CAST(NULL AS bigint) n FROM lineitem "
                       "WHERE quantity < 2")
    from decimal import Decimal
    assert isinstance(r.rows[0][0], Decimal)
    assert r.rows[0][1] is None


def test_multi_chunk_paging(coordinator, client):
    old = DispatchManager.RESULT_CHUNK_ROWS
    DispatchManager.RESULT_CHUNK_ROWS = 10
    try:
        r = client.execute("SELECT orderkey FROM orders "
                           "WHERE orderkey <= 120 ORDER BY orderkey")
    finally:
        DispatchManager.RESULT_CHUNK_ROWS = old
    assert len(r.rows) > 10                     # paged across several chunks
    assert r.rows == sorted(r.rows)


def test_error_propagates(client):
    with pytest.raises(QueryError):
        client.execute("SELECT no_such_column FROM lineitem")


def test_session_properties_flow(coordinator):
    c = StatementClient(coordinator.uri, schema="sf0.01",
                        session={"task_batch_rows": "4096"})
    r = c.execute("SELECT count(*) c FROM lineitem")
    assert r.rows[0][0] > 0


def test_cancel_requires_slug(coordinator, client):
    import urllib.error
    import urllib.request
    r = client.execute("SELECT 1 x")
    # DELETE without the per-query slug must not cancel (404: no such route)
    req = urllib.request.Request(
        f"{coordinator.uri}/v1/statement/{r.query_id}", method="DELETE")
    with pytest.raises(urllib.error.HTTPError) as ei:
        urllib.request.urlopen(req, timeout=10)
    assert ei.value.code == 404
    # wrong slug on the full path is rejected too
    req = urllib.request.Request(
        f"{coordinator.uri}/v1/statement/queued/{r.query_id}/badslug/0",
        method="DELETE")
    with pytest.raises(urllib.error.HTTPError) as ei:
        urllib.request.urlopen(req, timeout=10)
    assert ei.value.code == 404


def test_query_info_endpoint(coordinator, client):
    r = client.execute("SELECT 1 x")
    import json
    import urllib.request
    with urllib.request.urlopen(
            f"{coordinator.uri}/v1/query/{r.query_id}") as resp:
        info = json.loads(resp.read())
    assert info["state"] == "FINISHED"
    assert "resourceGroups" in info
    with urllib.request.urlopen(f"{coordinator.uri}/v1/query") as resp:
        listing = json.loads(resp.read())
    assert any(q["queryId"] == r.query_id for q in listing)


def test_statement_over_http_workers():
    """Full stack: client -> coordinator statement protocol -> distributed
    scheduling over announced HTTP workers (task protocol + exchange)."""
    coordinator = WorkerServer(coordinator=True, environment="test",
                               config=ExecutionConfig(batch_rows=1 << 13))
    workers = [WorkerServer(discovery_uri=coordinator.uri,
                            announce_interval_s=0.1, environment="test",
                            config=ExecutionConfig(batch_rows=1 << 13))
               for _ in range(2)]
    try:
        deadline = time.time() + 10
        while len(coordinator.worker_uris()) < 2 and time.time() < deadline:
            time.sleep(0.05)
        c = StatementClient(coordinator.uri, schema="sf0.01")
        r = c.execute("SELECT returnflag, sum(quantity) sq FROM lineitem "
                      "GROUP BY returnflag ORDER BY returnflag")
        assert len(r.rows) == 3
        assert r.stats["state"] == "FINISHED"
    finally:
        for w in workers:
            w.close()
        coordinator.close()


# ---------------------------------------------------------------------------
# dispatch / resource groups (unit level, fake executor)
# ---------------------------------------------------------------------------

class _FakeResult:
    column_names = ["x"]
    column_types = ["bigint"]
    rows = [[1]]


def _slow_executor(release: threading.Event):
    def run(q):
        release.wait(5)
        return _FakeResult()
    return run


def test_queueing_and_release():
    gate = threading.Event()
    rgm = ResourceGroupManager(
        [ResourceGroupSpec("g", hard_concurrency_limit=1, max_queued=1)],
        [Selector(group="g")])
    d = DispatchManager(_slow_executor(gate), rgm)
    q1 = d.submit("s1")
    q2 = d.submit("s2")
    time.sleep(0.1)
    assert q1.state == RUNNING
    assert q2.state == QUEUED
    # queue full -> immediate failure (QUERY_QUEUE_FULL analog)
    q3 = d.submit("s3")
    assert q3.state == FAILED and "queued" in q3.error.lower()
    gate.set()
    assert q1.done.wait(5) and q1.state == FINISHED
    assert q2.done.wait(5) and q2.state == FINISHED


def test_finished_queries_stay_readable_up_to_the_history_limit():
    gate = threading.Event()
    gate.set()
    d = DispatchManager(_slow_executor(gate))
    assert d.MAX_QUERY_HISTORY >= 400       # benchmark: query_info_limit
    qs = [d.submit(f"s{i}") for i in range(d.MAX_QUERY_HISTORY + 50)]
    assert all(q.done.wait(5) for q in qs)
    d.submit("one more")                    # eviction runs at submit
    with pytest.raises(KeyError):
        d.get(qs[0].query_id)               # the oldest finished ones go
    assert d.get(qs[60].query_id) is qs[60]
    assert len(d.list_queries()) <= d.MAX_QUERY_HISTORY + 1


def test_cancel_queued():
    gate = threading.Event()
    rgm = ResourceGroupManager(
        [ResourceGroupSpec("g", hard_concurrency_limit=1, max_queued=5)],
        [Selector(group="g")])
    d = DispatchManager(_slow_executor(gate), rgm)
    q1 = d.submit("s1")
    q2 = d.submit("s2")
    d.cancel(q2.query_id)
    assert q2.state == "CANCELED"
    gate.set()
    assert q1.done.wait(5)


def test_cancel_queued_does_not_over_admit():
    """Cancelling a QUEUED query must not free a slot it never held."""
    gate = threading.Event()
    rgm = ResourceGroupManager(
        [ResourceGroupSpec("g", hard_concurrency_limit=1, max_queued=5)],
        [Selector(group="g")])
    d = DispatchManager(_slow_executor(gate), rgm)
    q1 = d.submit("s1")
    q2 = d.submit("s2")
    q3 = d.submit("s3")
    d.cancel(q2.query_id)
    time.sleep(0.1)
    info = rgm.info()["g"]
    assert info["running"] <= 1
    assert q3.state == QUEUED          # q3 must not start while q1 runs
    gate.set()
    assert q1.done.wait(5) and q3.done.wait(5)


def test_canceled_query_reports_error():
    gate = threading.Event()
    rgm = ResourceGroupManager(
        [ResourceGroupSpec("g", hard_concurrency_limit=1, max_queued=5)],
        [Selector(group="g")])
    d = DispatchManager(_slow_executor(gate), rgm)
    q1 = d.submit("s1")
    q2 = d.submit("s2")
    d.cancel(q2.query_id)
    resp = d.executing_response(q2, 0, "http://x")
    assert resp["error"]["errorName"] == "USER_CANCELED"
    gate.set()
    q1.done.wait(5)


def test_selector_routing():
    rgm = ResourceGroupManager(
        [ResourceGroupSpec("etl"), ResourceGroupSpec("adhoc")],
        [Selector(group="etl", source="etl-.*"),
         Selector(group="adhoc")])
    assert rgm.select("alice", "etl-nightly") == "etl"
    assert rgm.select("alice", "dashboard") == "adhoc"


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def test_format_table():
    out = format_table(["a", "bb"], [[1, "xy"], [None, "z"]])
    lines = out.splitlines()
    assert lines[0].split("|")[0].strip() == "a"
    assert "NULL" in lines[3]
    assert len({len(l) for l in lines}) == 1    # aligned widths


def test_cli_run_statement(client, capsys):
    import io
    buf = io.StringIO()
    ok = run_statement(client, "SELECT 1 one, 2 two", out=buf)
    assert ok
    text = buf.getvalue()
    assert "one" in text and "1 row" in text
    assert not run_statement(client, "SELECT bogus FROM lineitem", out=buf)
