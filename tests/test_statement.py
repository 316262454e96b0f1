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


def _executing_gets(client):
    """Make `client` log the tokens of its GET .../executing requests."""
    tokens = []
    real = client._request

    def logged(url, method="GET", data=None, _hops=0):
        if method == "GET" and "/v1/statement/executing/" in url:
            tokens.append(int(url.rsplit("/", 1)[1]))
        return real(url, method, data, _hops)
    client._request = logged
    return tokens


@pytest.mark.parametrize("rows,gets", [(5, 1), (10, 2), (20, 3), (25, 3)])
def test_paging_ends_with_the_short_chunk(coordinator, monkeypatch, rows,
                                          gets):
    """A pull that comes back short is the last: its response is final.  A
    result of an exact multiple of the chunk keeps its one empty pull."""
    c = StatementClient(coordinator.uri, schema="sf0.01")
    tokens = _executing_gets(c)
    monkeypatch.setattr(DispatchManager, "RESULT_CHUNK_ROWS", 10)
    r = c.execute("SELECT orderkey FROM orders ORDER BY orderkey "
                  f"LIMIT {rows}")
    assert len(r.rows) == rows and r.rows == sorted(r.rows)
    assert r.stats["state"] == "FINISHED"
    assert tokens == list(range(gets))


def _hold_executor(dispatch, monkeypatch, seconds):
    """Every query's executor starts `seconds` late: a client's first GET
    .../executing/0 then beats the hand-over of the result."""
    real = dispatch._executor

    def late(q):
        time.sleep(seconds)
        return real(q)
    monkeypatch.setattr(dispatch, "_executor", late)


def _query_info(coordinator, query_id):
    import json
    import urllib.request
    with urllib.request.urlopen(
            f"{coordinator.uri}/v1/query/{query_id}") as resp:
        return json.loads(resp.read())


def test_first_poll_is_woken_by_the_hand_over(coordinator, monkeypatch):
    """A solo single-node SELECT streams: its first GET arrives before the
    executor has handed the row iterator over, is woken by the hand-over
    and runs the query in the same request."""
    c = StatementClient(coordinator.uri, schema="sf0.01")
    sql = ("SELECT returnflag, count(*) c FROM lineitem "
           "GROUP BY returnflag ORDER BY returnflag")
    c.execute(sql)                          # plan and programs are warm
    _hold_executor(coordinator.dispatch, monkeypatch, 0.1)
    tokens = _executing_gets(c)
    r = c.execute(sql)
    assert len(r.rows) == 3 and r.stats["state"] == "FINISHED"
    assert tokens == [0]
    stats = _query_info(coordinator, r.query_id)["runtimeStats"]
    # POST's answer is no poll; at most one GET .../queued before the one
    # GET .../executing, and none of them ran out its wait
    assert 1 <= stats["statementPolls"]["sum"] <= 2
    assert stats["statementPollTimeouts"]["sum"] == 0
    assert stats["statementPollWaitWallNanos"]["sum"] < 0.5e9


def test_sub_chunk_result_is_final_in_its_first_response(coordinator,
                                                         monkeypatch):
    d = coordinator.dispatch
    _hold_executor(d, monkeypatch, 0.1)
    q = d.submit("SELECT returnflag, count(*) c FROM lineitem "
                 "GROUP BY returnflag ORDER BY returnflag")
    t0 = time.perf_counter()
    resp = d.executing_response(q, 0, coordinator.uri, wait_s=60.0)
    # the poll ends with the hand-over and the query, not with its wait
    assert time.perf_counter() - t0 < 30.0
    assert "nextUri" not in resp and "error" not in resp
    assert len(resp["data"]) == 3
    assert resp["stats"]["state"] == FINISHED and q.done.is_set()
    assert q.runtime_stats["queryExecuteWallNanos"]["count"] == 1
    assert q.runtime_stats["statementPollTimeouts"]["sum"] == 0
    # the client may ask for its current token again
    again = d.executing_response(q, 0, coordinator.uri)
    assert again["data"] == resp["data"] and "nextUri" not in again
    assert again["stats"]["state"] == FINISHED


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
    info = _query_info(coordinator, r.query_id)
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


def test_failure_before_the_hand_over_wakes_the_poll():
    def fails(q):
        time.sleep(0.1)
        raise ValueError("no such column")
    d = DispatchManager(fails)
    q = d.submit("s1")
    t0 = time.perf_counter()
    resp = d.executing_response(q, 0, "http://x", wait_s=60.0)
    assert time.perf_counter() - t0 < 30.0
    assert "no such column" in resp["error"]["message"]
    assert resp["error"]["errorName"] == "QUERY_FAILED"
    assert resp["stats"]["state"] == FAILED and "nextUri" not in resp
    assert q.runtime_stats["statementPolls"]["sum"] == 1
    assert q.runtime_stats["statementPollTimeouts"]["sum"] == 0


def test_queued_poll_is_woken_when_the_query_starts():
    gate1, gate2 = threading.Event(), threading.Event()
    gates = {"s1": gate1, "s2": gate2}

    def run(q):
        gates[q.sql].wait(60)
        return _FakeResult()
    rgm = ResourceGroupManager(
        [ResourceGroupSpec("g", hard_concurrency_limit=1, max_queued=5)],
        [Selector(group="g")])
    d = DispatchManager(run, rgm)
    q1 = d.submit("s1")
    q2 = d.submit("s2")
    assert q2.state == QUEUED
    threading.Timer(0.1, gate1.set).start()
    t0 = time.perf_counter()
    resp = d.queued_response(q2, 1, "http://x", wait_s=60.0)
    try:
        # q2 is RUNNING, not done: its start ended the poll
        assert time.perf_counter() - t0 < 30.0
        assert resp["stats"]["state"] == RUNNING and not q2.done.is_set()
        assert resp["nextUri"].endswith(f"/executing/{q2.query_id}/"
                                        f"{q2.slug}/0")
        assert q2.runtime_stats["statementPollTimeouts"]["sum"] == 0
    finally:
        gate2.set()
    assert q1.done.wait(5) and q2.done.wait(5)


def test_poll_that_runs_out_is_counted():
    gate = threading.Event()
    d = DispatchManager(_slow_executor(gate))
    q = d.submit("s1")
    # the POST's own answer is no poll
    d.queued_response(q, 0, "http://x", wait_s=0.0)
    assert q.runtime_stats is None \
        or "statementPolls" not in q.runtime_stats
    resp = d.executing_response(q, 0, "http://x", wait_s=0.05)
    assert resp["nextUri"].endswith("/0")       # the same token again
    gate.set()
    resp = d.executing_response(q, 0, "http://x", wait_s=60.0)
    assert resp["data"] == [[1]] and "nextUri" not in resp
    assert q.runtime_stats["statementPolls"]["sum"] == 2
    assert q.runtime_stats["statementPollTimeouts"]["sum"] == 1


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
