"""Telemetry export pipeline tests (tier-1).

Covers the PR's acceptance surface:
  * OTLP golden schemas: span / metric payload shapes out of the pure
    converters, deterministic (token, name) -> id stitching.
  * exporter backpressure: the bounded queue DROPS (metered) and never
    blocks the caller; sink outages retry under the jittered error
    budget, then drop.
  * history retention: count + age eviction (injectable clock), restart
    reload from the JSONL spool, malformed-line tolerance.
  * /v1/cluster + /v1/query?state=... + history survival across a
    coordinator restart, over real loopback HTTP.
  * the end-to-end distributed trace: a client trace token yields ONE
    OTLP trace holding coordinator query/fragment spans and worker
    task/operator spans.
  * per-query device profiler capture smoke under the CPU backend.
"""
import json
import os
import threading
import time
import urllib.error
import urllib.request

import pytest

from presto_tpu.telemetry import (CollectorSink, HistoryEventListener,
                                  JsonlFileSink, QueryHistoryStore,
                                  TelemetryExporter, make_sink,
                                  metrics_to_resource_metrics,
                                  profile_capture, scrape_metric_points,
                                  span_id_for, spans_to_resource_spans,
                                  trace_id_for)
from presto_tpu.utils.runtime_stats import Span


def _get_json(url):
    with urllib.request.urlopen(url, timeout=10) as resp:
        return json.loads(resp.read())


# ---------------------------------------------------------------------------
# OTLP golden schemas
# ---------------------------------------------------------------------------

def test_trace_and_span_ids_deterministic():
    assert trace_id_for("tok") == trace_id_for("tok")
    assert trace_id_for("tok") != trace_id_for("tok2")
    assert len(trace_id_for("tok")) == 32          # 16 bytes hex
    assert len(span_id_for("tok", "query")) == 16  # 8 bytes hex
    # the stitching property: two processes that only share the token
    # agree on every span id
    assert span_id_for("tok", "fragment 1") == span_id_for("tok",
                                                           "fragment 1")


def test_spans_to_resource_spans_golden_shape():
    spans = [
        Span("query", "", start=10.0, end=11.5,
             attributes={"sql": "select 1", "rows": 3, "ok": True,
                         "frac": 0.5}),
        Span("fragment 0", "query", start=10.1, end=11.0),
    ]
    payload = spans_to_resource_spans("tok", spans,
                                      resource={"service.name": "p"})
    (rs,) = payload["resourceSpans"]
    assert rs["resource"]["attributes"] == [
        {"key": "service.name", "value": {"stringValue": "p"}}]
    (ss,) = rs["scopeSpans"]
    assert ss["scope"]["name"] == "presto_tpu.telemetry"
    root, frag = ss["spans"]
    assert root["traceId"] == frag["traceId"] == trace_id_for("tok")
    assert root["parentSpanId"] == ""
    assert frag["parentSpanId"] == root["spanId"]
    assert root["spanId"] == span_id_for("tok", "query")
    assert root["startTimeUnixNano"] == str(int(10.0 * 1e9))
    assert root["endTimeUnixNano"] == str(int(11.5 * 1e9))
    attrs = {a["key"]: a["value"] for a in root["attributes"]}
    # OTLP/JSON AnyValue: intValue is a decimal STRING; bools are bools
    assert attrs["sql"] == {"stringValue": "select 1"}
    assert attrs["rows"] == {"intValue": "3"}
    assert attrs["ok"] == {"boolValue": True}
    assert attrs["frac"] == {"doubleValue": 0.5}
    json.dumps(payload)   # wire-encodable as-is


def test_metrics_payload_golden_shape():
    payload = metrics_to_resource_metrics(
        [("presto_tpu.exchange.bytes", 42.0, {}),
         ("presto_tpu.serving.servingBatchOccupancy", 2.0,
          {"occupancy": "4"})],
        time_unix_nano=123, resource={"service.name": "p"})
    (rm,) = payload["resourceMetrics"]
    (sm,) = rm["scopeMetrics"]
    m0, m1 = sm["metrics"]
    assert m0["name"] == "presto_tpu.exchange.bytes"
    assert m0["gauge"]["dataPoints"] == [
        {"timeUnixNano": "123", "asDouble": 42.0}]
    (dp,) = m1["gauge"]["dataPoints"]
    assert dp["attributes"] == [
        {"key": "occupancy", "value": {"stringValue": "4"}}]
    json.dumps(payload)


def test_scrape_covers_every_registry():
    names = {n for n, _v, _a in scrape_metric_points()}
    for prefix in ("presto_tpu.exchange.", "presto_tpu.exchange_fabric.",
                   "presto_tpu.serving.", "presto_tpu.storage.",
                   "presto_tpu.memory."):
        assert any(n.startswith(prefix) for n in names), prefix
    assert not any(n.startswith("presto_tpu.kernel.") for n in names)
    assert "presto_tpu.memory.spilled_bytes" in names


def test_make_sink_dispatch(tmp_path):
    assert make_sink("none") is None
    assert make_sink("") is None
    assert isinstance(make_sink("collector"), CollectorSink)
    assert isinstance(make_sink("jsonl", path=str(tmp_path / "t.jsonl")),
                      JsonlFileSink)
    with pytest.raises(ValueError):
        make_sink("jsonl")             # needs a path
    with pytest.raises(ValueError):
        make_sink("http")              # needs an endpoint
    with pytest.raises(ValueError):
        make_sink("bogus")


# ---------------------------------------------------------------------------
# exporter: batching, backpressure, retry budget
# ---------------------------------------------------------------------------

def test_exporter_delivers_spans_and_metrics():
    sink = CollectorSink()
    exp = TelemetryExporter(sink, queue_bound=16, flush_interval_s=0.02)
    try:
        exp.export_spans("tok", [Span("query", "", start=1.0, end=2.0)],
                         resource={"presto.role": "coordinator"})
        exp.scrape_metrics()
        assert exp.flush(timeout_s=5.0)
        assert sink.trace_ids() == [trace_id_for("tok")]
        assert "presto_tpu.serving.planCacheHits" in sink.metric_names()
        c = exp.counters()
        assert c["enqueued"] == 2 and c["exported"] == 2
        assert c["dropped"] == 0 and c["queue_depth"] == 0
    finally:
        exp.close()


def test_exporter_backpressure_drops_metered_never_blocks():
    """A wedged sink must not wedge the query path: enqueue stays
    wait-free, overflow is dropped and counted."""
    release = threading.Event()

    class StallingSink(CollectorSink):
        def export(self, payload):
            release.wait(10)
            super().export(payload)

    exp = TelemetryExporter(StallingSink(), queue_bound=4,
                            flush_interval_s=0.01)
    try:
        t0 = time.perf_counter()
        results = [exp.enqueue({"resourceSpans": [], "i": i})
                   for i in range(32)]
        elapsed = time.perf_counter() - t0
        assert elapsed < 1.0, "enqueue must never block on a stalled sink"
        c = exp.counters()
        # bound + at most one in flight survive; the rest dropped
        assert c["dropped"] >= 32 - 4 - 1
        assert c["dropped"] + c["enqueued"] == 32
        assert results.count(False) == c["dropped"]
        release.set()
        assert exp.flush(timeout_s=5.0)
        assert exp.counters()["exported"] == c["enqueued"]
    finally:
        release.set()
        exp.close()


def test_exporter_retry_budget_then_drop():
    """Sink failures retry with backoff under the error budget, then the
    payload is dropped (metered) instead of wedging the flush thread."""
    class DeadSink(CollectorSink):
        def __init__(self):
            super().__init__()
            self.attempts = 0

        def export(self, payload):
            self.attempts += 1
            raise OSError("collector down")

    sink = DeadSink()
    exp = TelemetryExporter(sink, queue_bound=4, flush_interval_s=0.01,
                            max_error_duration_s=0.3)
    try:
        assert exp.enqueue({"resourceSpans": []})
        deadline = time.monotonic() + 10
        while (exp.counters()["dropped_after_retry"] == 0
               and time.monotonic() < deadline):
            time.sleep(0.02)
        c = exp.counters()
        assert c["dropped_after_retry"] == 1
        assert c["retries"] >= 1 and sink.attempts >= 2
        assert c["exported"] == 0
    finally:
        exp.close()


def test_exporter_rejects_unbounded_queue():
    with pytest.raises(ValueError):
        TelemetryExporter(CollectorSink(), queue_bound=0)


def test_jsonl_sink_appends_lines(tmp_path):
    path = str(tmp_path / "telemetry.jsonl")
    exp = TelemetryExporter(JsonlFileSink(path), queue_bound=8,
                            flush_interval_s=0.01)
    try:
        exp.export_spans("tok", [Span("query", "", start=1.0, end=2.0)])
        exp.export_spans("tok2", [Span("query", "", start=1.0, end=2.0)])
        assert exp.flush(timeout_s=5.0)
    finally:
        exp.close()
    lines = [json.loads(l) for l in open(path) if l.strip()]
    assert len(lines) == 2
    assert all("resourceSpans" in l for l in lines)


# ---------------------------------------------------------------------------
# history store: retention + restart reload
# ---------------------------------------------------------------------------

def _rec(qid, state="FINISHED", **kw):
    return {"queryId": qid, "state": state, "query": f"select {qid}", **kw}


def test_history_count_eviction():
    store = QueryHistoryStore(max_count=3)
    for i in range(5):
        store.record(_rec(f"q{i}"))
    assert len(store) == 3
    assert [r["queryId"] for r in store.list()] == ["q4", "q3", "q2"]
    assert store.evicted == 2


def test_history_age_eviction_with_injected_clock():
    now = [1000.0]
    store = QueryHistoryStore(max_count=100, max_age_s=60.0,
                              clock=lambda: now[0])
    store.record(_rec("old"))
    now[0] += 120.0
    store.record(_rec("fresh"))
    assert [r["queryId"] for r in store.list()] == ["fresh"]
    assert store.evicted == 1
    assert store.counts_by_state() == {"FINISHED": 1}


def test_history_state_filter_and_rerecord():
    store = QueryHistoryStore(max_count=10)
    store.record(_rec("a", state="FAILED"))
    store.record(_rec("b"))
    store.record(_rec("a", state="FINISHED"))   # supersedes
    assert [r["queryId"] for r in store.list(state="finished")] == ["a",
                                                                    "b"]
    assert store.list(state="FAILED") == []
    assert store.get("a")["state"] == "FINISHED"


def test_history_restart_reload(tmp_path):
    path = str(tmp_path / "history.jsonl")
    store = QueryHistoryStore(path, max_count=10)
    store.record(_rec("q1"))
    store.record(_rec("q2", state="FAILED", errorMessage="boom"))
    del store

    reloaded = QueryHistoryStore(path, max_count=10)
    assert reloaded.loaded == 2
    assert reloaded.get("q2")["errorMessage"] == "boom"
    assert [r["queryId"] for r in reloaded.list()] == ["q2", "q1"]
    # retention applies at reload too: a tighter bound compacts the spool
    tight = QueryHistoryStore(path, max_count=1)
    assert len(tight) == 1 and tight.get("q2") is not None
    assert sum(1 for _ in open(path)) == 1      # compacted on load


def test_history_tolerates_malformed_lines(tmp_path):
    path = str(tmp_path / "history.jsonl")
    with open(path, "w") as f:
        f.write(json.dumps(_rec("good")) + "\n")
        f.write("{not json\n")
        f.write(json.dumps({"noQueryId": True}) + "\n")
    store = QueryHistoryStore(path, max_count=10)
    assert store.loaded == 1 and store.load_errors == 2
    assert store.get("good") is not None


def test_history_listener_records_completed_events():
    from presto_tpu.worker.events import QueryCompletedEvent
    store = QueryHistoryStore(max_count=10)
    listener = HistoryEventListener(
        store, extra_fields=lambda ev: {"profileTraceDir": "/tmp/x"})
    listener.query_completed(QueryCompletedEvent(
        query_id="q1", sql="select 1", user="u", state="FINISHED",
        create_time=1.0, end_time=2.0, wall_time_s=1.0, queued_time_s=0.0,
        rows=1, trace_token="tok", resource_group="global"))
    rec = store.get("q1")
    assert rec["traceToken"] == "tok"
    assert rec["resourceGroup"] == "global"
    assert rec["profileTraceDir"] == "/tmp/x"


# ---------------------------------------------------------------------------
# profiler capture (CPU-backend smoke)
# ---------------------------------------------------------------------------

def test_profile_capture_disabled_paths(tmp_path):
    with profile_capture(str(tmp_path), "q", enabled=False) as d:
        assert d is None
    with profile_capture(None, "q", enabled=True) as d:
        assert d is None


def test_profile_capture_smoke(tmp_path):
    import jax
    import jax.numpy as jnp
    with profile_capture(str(tmp_path), "q0.1", enabled=True) as d:
        assert d is not None and d.startswith(str(tmp_path))
        jax.jit(lambda x: x * 2)(jnp.arange(8)).block_until_ready()  # lint: allow-host-sync
    assert os.path.isdir(d)
    # jax wrote SOMETHING under the capture dir (plugin layout varies)
    assert any(files for _root, _dirs, files in os.walk(d))


def test_profile_capture_concurrent_loser_degrades(tmp_path):
    with profile_capture(str(tmp_path), "winner", enabled=True) as d1:
        assert d1 is not None
        with profile_capture(str(tmp_path), "loser", enabled=True) as d2:
            assert d2 is None   # singleton profiler session: no queueing


def test_explain_analyze_footer_reports_profile_dir(tmp_path):
    from presto_tpu.exec.pipeline import ExecutionConfig
    from presto_tpu.exec.runner import LocalQueryRunner
    runner = LocalQueryRunner("sf0.01", config=ExecutionConfig(
        profile=True, profile_dir=str(tmp_path)))
    res = runner.execute("EXPLAIN ANALYZE select count(*) from nation")
    text = res.rows[0][0]
    assert "Device profile: " in text
    reported = text.split("Device profile: ", 1)[1].splitlines()[0]
    assert os.path.isdir(reported)


def test_query_result_carries_profile_trace_dir(tmp_path):
    from presto_tpu.exec.pipeline import ExecutionConfig
    from presto_tpu.exec.runner import LocalQueryRunner
    runner = LocalQueryRunner("sf0.01", config=ExecutionConfig(
        profile=True, profile_dir=str(tmp_path)))
    res = runner.execute("select count(*) from nation")
    assert res.profile_trace_dir and os.path.isdir(res.profile_trace_dir)
    # and off by default
    res2 = LocalQueryRunner("sf0.01").execute("select 1")
    assert res2.profile_trace_dir is None


# ---------------------------------------------------------------------------
# server integration: /v1/cluster, /v1/query, restart survival, e2e trace
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def traced_cluster():
    """Coordinator (collector-sinked telemetry + history) + 2 workers."""
    from presto_tpu.worker.server import WorkerServer
    sink = CollectorSink()
    coordinator = WorkerServer(coordinator=True, environment="test",
                               telemetry_sink=sink,
                               telemetry_flush_interval_s=0.02)
    workers = [WorkerServer(discovery_uri=coordinator.uri,
                            announce_interval_s=0.1,
                            environment="test") for _ in range(2)]
    deadline = time.time() + 10
    while len(coordinator.worker_uris()) < 2 and time.time() < deadline:
        time.sleep(0.05)
    assert len(coordinator.worker_uris()) == 2, "workers failed to announce"
    yield coordinator, workers, sink
    for w in workers:
        w.close()
    coordinator.close()


def test_end_to_end_distributed_trace(traced_cluster):
    """The acceptance bar: a client-supplied X-Presto-Trace-Token yields
    ONE OTLP trace containing the coordinator's query/fragment spans AND
    the workers' task/operator spans, with nothing dropped."""
    from presto_tpu.client import StatementClient
    coordinator, _workers, sink = traced_cluster
    token = "e2e-trace-0001"
    client = StatementClient(coordinator.uri, schema="sf0.01",
                             trace_token=token)
    res = client.execute(
        "select n_regionkey, count(*) from nation group by n_regionkey")
    assert len(res.rows) == 5
    assert coordinator.telemetry.flush(timeout_s=10.0)

    spans = [s for s in sink.spans()
             if s["traceId"] == trace_id_for(token)]
    by_name = {s["name"]: s for s in spans}
    assert "query" in by_name, sorted(by_name)
    fragments = [s for s in spans if s["name"].startswith("fragment ")]
    tasks = [s for s in spans if s["name"].startswith("task ")]
    operators = [s for s in spans if s["name"].startswith("operator ")]
    assert fragments and tasks and operators
    # stitch check: every fragment hangs off the query root; every task's
    # parent id equals SOME exported fragment span id even though the
    # worker slice was exported by a different server object
    qid = by_name["query"]["spanId"]
    assert all(f["parentSpanId"] == qid for f in fragments)
    frag_ids = {f["spanId"] for f in fragments}
    assert all(t["parentSpanId"] in frag_ids for t in tasks)
    task_ids = {t["spanId"] for t in tasks}
    assert all(o["parentSpanId"] in task_ids for o in operators)
    # distributed provenance: coordinator and worker resource slices
    roles = set()
    for p in sink.payloads:
        for rs in p.get("resourceSpans", []):
            for a in rs["resource"]["attributes"]:
                if a["key"] == "presto.role":
                    roles.add(a["value"]["stringValue"])
    assert {"coordinator", "worker"} <= roles
    # real intervals: a task's recorded phases are spans of their own
    # inside it (the old export stamped the task's interval on every one)
    def interval(s):
        return int(s["startTimeUnixNano"]), int(s["endTimeUnixNano"])
    for t in tasks:
        phases = [s for s in spans if s["parentSpanId"] == t["spanId"]
                  and not s["name"].startswith("operator ")]
        assert {p["name"].split(" ")[0] for p in phases} >= {
            "pipelineBuild", "pipelineDrain", "taskSerialize"}
        assert interval(t) not in {interval(p) for p in phases}
        assert len({interval(p) for p in phases}) == len(phases)
        assert all(interval(t)[0] - 50_000_000 <= interval(p)[0]
                   <= interval(p)[1] <= interval(t)[1] + 50_000_000
                   for p in phases)
    c = coordinator.telemetry.counters()
    assert c["dropped"] == 0 and c["dropped_after_retry"] == 0


def test_http_explain_analyze_profile_footer(traced_cluster, tmp_path):
    """`profile=true` captures through the HTTP-distributed ANALYZE path
    (coordinator _explain_http), not just the local runner."""
    from presto_tpu.client import StatementClient
    coordinator, _workers, _sink = traced_cluster
    client = StatementClient(coordinator.uri, schema="sf0.01",
                             session={"profile": "true"})
    res = client.execute("EXPLAIN ANALYZE select count(*) from nation")
    text = res.rows[0][0]
    assert "Device profile: " in text, text[-300:]
    reported = text.split("Device profile: ", 1)[1].splitlines()[0]
    assert os.path.isdir(reported)


def test_cluster_endpoint_shape(traced_cluster):
    coordinator, _workers, _sink = traced_cluster
    info = _get_json(f"{coordinator.uri}/v1/cluster")
    for key in ("runningQueries", "queuedQueries", "blockedQueries",
                "finishedQueries", "failedQueries", "activeWorkers",
                "runningTasks", "totalTasks", "reservedMemoryBytes",
                "fabricByteRates", "historyEntries", "telemetry"):
        assert key in info, key
    assert info["activeWorkers"] == 2
    assert info["finishedQueries"] >= 1   # the e2e query above
    assert isinstance(info["fabricByteRates"], dict)
    assert info["telemetry"]["queue_bound"] > 0


def test_cluster_endpoint_is_coordinator_only(traced_cluster):
    _coordinator, workers, _sink = traced_cluster
    with pytest.raises(urllib.error.HTTPError) as e:
        urllib.request.urlopen(f"{workers[0].uri}/v1/cluster", timeout=10)
    assert e.value.code == 404


def test_query_list_state_filter(traced_cluster):
    coordinator, _workers, _sink = traced_cluster
    finished = _get_json(f"{coordinator.uri}/v1/query?state=FINISHED")
    assert finished and all(q["state"] == "FINISHED" for q in finished)
    assert not _get_json(f"{coordinator.uri}/v1/query?state=CANCELED")


def test_history_survives_coordinator_restart(tmp_path):
    from presto_tpu.client import StatementClient
    from presto_tpu.worker.server import WorkerServer
    hist = str(tmp_path / "history.jsonl")
    server = WorkerServer(coordinator=True, environment="test",
                          history_path=hist)
    try:
        client = StatementClient(server.uri, schema="sf0.01")
        res = client.execute("select count(*) from nation")
        assert res.rows == [[25]]
        qids = [q["queryId"] for q in
                _get_json(f"{server.uri}/v1/query?state=FINISHED")]
        assert len(qids) == 1
    finally:
        server.close()

    revived = WorkerServer(coordinator=True, environment="test",
                           history_path=hist)
    try:
        assert revived.history.loaded == 1
        listed = _get_json(f"{revived.uri}/v1/query?state=FINISHED")
        assert [q["queryId"] for q in listed] == qids
        # /v1/query/{id} falls back to the durable record
        rec = _get_json(f"{revived.uri}/v1/query/{qids[0]}")
        assert rec["source"] == "history"
        assert rec["state"] == "FINISHED"
    finally:
        revived.close()


def test_server_metrics_expose_telemetry_counters(traced_cluster):
    coordinator, _workers, _sink = traced_cluster
    with urllib.request.urlopen(f"{coordinator.uri}/v1/metrics",
                                timeout=10) as resp:
        body = resp.read().decode()
    assert "presto_tpu_telemetry_enqueued_total" in body
    assert "presto_tpu_telemetry_dropped_total 0" in body
    assert "presto_tpu_history_entries" in body
