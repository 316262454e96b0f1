"""Event-listener pipeline + properties-file configuration (VERDICT r2 #9).

Reference analogs: QueryMonitor.java:106 (created/completed events to
every registered EventListener), EventListenerManager (listener failure
isolation), Configs.h / NodeConfig (config.properties / node.properties),
CatalogManager (etc/catalog/*.properties connector mounts).
"""
import json
import os

import pytest

from presto_tpu.exec.pipeline import ExecutionConfig
from presto_tpu.worker.events import (EventListener, EventListenerManager,
                                      FileEventListener)
from presto_tpu.worker.properties import (SystemConfig,
                                          execution_config_from_properties,
                                          load_properties,
                                          register_catalogs_from_etc,
                                          server_kwargs_from_etc)


class _Recorder(EventListener):
    def __init__(self):
        self.created = []
        self.completed = []

    def query_created(self, e):
        self.created.append(e)

    def query_completed(self, e):
        self.completed.append(e)


class _Broken(EventListener):
    def query_created(self, e):
        raise RuntimeError("listener bug")

    def query_completed(self, e):
        raise RuntimeError("listener bug")


# ---------------------------------------------------------------------------
# properties parsing
# ---------------------------------------------------------------------------

def test_load_properties_format(tmp_path):
    p = tmp_path / "config.properties"
    p.write_text(
        "# comment\n"
        "! also comment\n"
        "coordinator=true\n"
        "colon.key: colon value\n"
        "spaced.key =  trimmed  \n"
        "continued.key=one\\\n"
        "two\n"
        "bare-flag\n")
    props = load_properties(str(p))
    assert props["coordinator"] == "true"
    assert props["colon.key"] == "colon value"
    assert props["spaced.key"] == "trimmed"
    assert props["continued.key"] == "onetwo"
    assert props["bare-flag"] == ""


def test_execution_config_mapping():
    cfg = execution_config_from_properties({
        "query.max-memory-per-node": "512MB",
        "experimental.spill-enabled": "false",
        "exchange.compression-enabled": "true",
        "exchange.compression-codec": "zstd",
        "task.batch-rows": "8192",
        "coordinator-only.key": "ignored",
    })
    assert cfg.memory_budget_bytes == 512 << 20
    assert cfg.spill_enabled is False
    assert cfg.exchange_compression is True
    assert cfg.exchange_compression_codec == "ZSTD"
    assert cfg.batch_rows == 8192
    with pytest.raises(ValueError, match="LZO"):
        execution_config_from_properties(
            {"exchange.compression-codec": "LZO"})


@pytest.mark.parametrize("key,value", [
    ("scan.kernel", "pallas"), ("scan.kernel-dma", "double"),
    ("scan_kernel", "pallas"), ("scan_kernel_dma", "double"),
])
def test_removed_scan_kernel_knobs_are_ignored(key, value):
    """A coordinator that still sends a knob of the deleted Pallas path is
    served as for any unknown key: a config key (dotted) or a session
    property (underscored) parses to the default ExecutionConfig."""
    from presto_tpu.worker.protocol import apply_session_properties
    parse = (execution_config_from_properties if "." in key
             else lambda kv: apply_session_properties(ExecutionConfig(), kv))
    assert parse({key: value}) == ExecutionConfig()


def test_no_configuration_name_mentions_a_kernel():
    import dataclasses
    fields = [f.name for f in dataclasses.fields(ExecutionConfig)]
    keys = [k for k, _t, _d in SystemConfig.KEYS]
    assert not [n for n in fields + keys if "kernel" in n]


def test_no_module_imports_pallas():
    """There is one scan path, the fused XLA chain: no module of the
    package imports Pallas, and analysis/lint.py alone names it (KERNEL001
    refuses an interpret=True literal wherever a kernel enters later)."""
    import presto_tpu
    root = os.path.dirname(presto_tpu.__file__)
    naming = []
    for dirpath, _dirs, files in os.walk(root):
        for f in files:
            if f.endswith(".py"):
                path = os.path.join(dirpath, f)
                with open(path, encoding="utf-8") as fh:
                    src = fh.read()
                assert "jax.experimental.pallas" not in src \
                    and "jax.experimental import pallas" not in src, path
                if "pallas" in src.lower():
                    naming.append(os.path.relpath(path, root))
    assert naming == [os.path.join("analysis", "lint.py")]


def _write_etc(tmp_path, extra_catalogs=()):
    etc = tmp_path / "etc"
    (etc / "catalog").mkdir(parents=True)
    (etc / "config.properties").write_text(
        "coordinator=true\n"
        "http-server.http.port=0\n"
        "query.max-memory-per-node=1GB\n")
    (etc / "node.properties").write_text(
        "node.environment=staging\n"
        "node.id=node-cfg-1\n")
    (etc / "catalog" / "mem.properties").write_text(
        "connector.name=memory\n")
    for name, body in extra_catalogs:
        (etc / "catalog" / f"{name}.properties").write_text(body)
    return str(etc)


def test_server_kwargs_from_etc(tmp_path):
    etc = _write_etc(tmp_path)
    kwargs, props = server_kwargs_from_etc(etc)
    assert kwargs["coordinator"] is True
    assert kwargs["port"] == 0
    assert kwargs["environment"] == "staging"
    assert kwargs["node_id"] == "node-cfg-1"
    assert kwargs["config"].memory_budget_bytes == 1 << 30
    assert props["node.environment"] == "staging"


def test_register_catalogs_from_etc(tmp_path):
    from presto_tpu.connectors import catalog as registry
    etc = _write_etc(tmp_path)
    mounted = register_catalogs_from_etc(etc)
    assert mounted == {"mem": "memory"}
    assert registry.module("mem") is not None
    registry.unregister_connector("mem")


def test_unknown_connector_rejected(tmp_path):
    etc = _write_etc(tmp_path, extra_catalogs=[
        ("bad", "connector.name=not-a-connector\n")])
    with pytest.raises(ValueError, match="not-a-connector"):
        register_catalogs_from_etc(etc)


# ---------------------------------------------------------------------------
# event pipeline
# ---------------------------------------------------------------------------

def _drain(dispatch, q, timeout=120):
    """Walk the statement protocol like a client (streaming results only
    complete when drained); returns accumulated data rows."""
    import time as _time
    rows = []
    deadline = _time.time() + timeout
    token = 0
    while _time.time() < deadline and not q.done.is_set():
        if q.state == "QUEUED":
            dispatch.queued_response(q, 0, "http://test")
            continue
        resp = dispatch.executing_response(q, token, "http://test")
        rows.extend(resp.get("data", []))
        if "nextUri" in resp:
            token = int(resp["nextUri"].rsplit("/", 1)[1])
        elif not q.done.is_set():
            break
    return rows


def test_dispatch_fires_created_and_completed():
    from presto_tpu.worker.server import WorkerServer
    rec = _Recorder()
    mgr = EventListenerManager()
    mgr.register(rec)
    w = WorkerServer(coordinator=True, events=mgr)
    try:
        q = w.dispatch.submit("select count(*) from nation",
                              user="alice", source="cli")
        assert _drain(w.dispatch, q) == [[25]]
        assert q.done.wait(60)
        assert [e.query_id for e in rec.created] == [q.query_id]
        assert rec.created[0].user == "alice"
        assert rec.created[0].sql == "select count(*) from nation"
        done = [e for e in rec.completed if e.query_id == q.query_id]
        assert len(done) == 1
        assert done[0].state == "FINISHED"
        assert done[0].error is None
        assert done[0].wall_time_s >= 0
    finally:
        w.close()


def test_failed_query_event_carries_error():
    from presto_tpu.worker.server import WorkerServer
    rec = _Recorder()
    mgr = EventListenerManager()
    mgr.register(rec)
    w = WorkerServer(coordinator=True, events=mgr)
    try:
        q = w.dispatch.submit("select no_such_column from nation")
        assert q.done.wait(60)
        done = [e for e in rec.completed if e.query_id == q.query_id]
        assert done[0].state == "FAILED"
        assert done[0].error
    finally:
        w.close()


def test_listener_failure_isolated():
    """A throwing listener must not fail the query nor starve the next
    listener (EventListenerManager dispatch isolation)."""
    rec = _Recorder()
    mgr = EventListenerManager()
    mgr.register(_Broken())
    mgr.register(rec)
    from presto_tpu.worker.server import WorkerServer
    w = WorkerServer(coordinator=True, events=mgr)
    try:
        q = w.dispatch.submit("select count(*) from region")
        _drain(w.dispatch, q)
        assert q.done.wait(60)
        assert q.state == "FINISHED"
        assert len(rec.created) == 1 and len(rec.completed) >= 1
        assert mgr.dispatch_errors >= 2
    finally:
        w.close()


def test_file_event_listener(tmp_path):
    path = str(tmp_path / "events.jsonl")
    lst = FileEventListener(path)
    mgr = EventListenerManager()
    mgr.register(lst)
    from presto_tpu.worker.server import WorkerServer
    w = WorkerServer(coordinator=True, events=mgr)
    try:
        q = w.dispatch.submit("select count(*) from nation")
        _drain(w.dispatch, q)
        assert q.done.wait(60)
    finally:
        w.close()
    lines = [json.loads(l) for l in open(path)]
    kinds = [l["event"] for l in lines]
    assert "query_created" in kinds and "query_completed" in kinds
    assert all(l["query_id"] == q.query_id for l in lines)


def test_worker_boots_from_etc_dir(tmp_path):
    """End to end: `python -m presto_tpu.worker --etc-dir etc/` boots a
    coordinator from the file layout, serves a statement query, and the
    configured file event listener records it."""
    import re
    import subprocess
    import sys
    import time
    import urllib.request

    etc = _write_etc(tmp_path)
    events_path = os.path.join(str(tmp_path), "events.jsonl")
    with open(os.path.join(etc, "event-listener.properties"), "w") as f:
        f.write("event-listener.name=file\n"
                f"event-listener.path={events_path}\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu", JAX_PLATFORM_NAME="cpu",
               PYTHONPATH=os.path.dirname(os.path.dirname(
                   os.path.abspath(__file__))))
    proc = subprocess.Popen(
        [sys.executable, "-m", "presto_tpu.worker", "--etc-dir", etc],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        env=env)
    try:
        line = proc.stdout.readline()
        m = re.search(r"(node-cfg-1) listening on (http://[\d.:]+)", line)
        assert m, f"node.id from node.properties not used: {line!r}"
        uri = m.group(2)
        req = urllib.request.Request(
            uri + "/v1/statement", data=b"select count(*) from region",
            headers={"X-Presto-User": "etc-test"})
        with urllib.request.urlopen(req, timeout=30) as r:
            d = json.loads(r.read())
        data = list(d.get("data", []))
        deadline = time.time() + 60
        while "nextUri" in d and time.time() < deadline:
            with urllib.request.urlopen(d["nextUri"], timeout=30) as r:
                d = json.loads(r.read())
            data.extend(d.get("data", []))
        assert data == [[5]], (data, d)
        deadline = time.time() + 10
        while time.time() < deadline:
            if os.path.exists(events_path) and any(
                    json.loads(l)["event"] == "query_completed"
                    for l in open(events_path)):
                break
            time.sleep(0.2)
        lines = [json.loads(l) for l in open(events_path)]
        assert any(l["event"] == "query_created"
                   and l["user"] == "etc-test" for l in lines)
        assert any(l["event"] == "query_completed"
                   and l["state"] == "FINISHED" for l in lines)
    finally:
        proc.terminate()
        proc.wait(timeout=10)


def test_queue_full_rejection_emits_completed_event():
    """A query rejected at admission must emit query_completed (FAILED),
    not dangle as created-only in the event stream."""
    from presto_tpu.worker.statement import (DispatchManager,
                                             ResourceGroupManager,
                                             ResourceGroupSpec)
    import threading
    rec = _Recorder()
    mgr = EventListenerManager()
    mgr.register(rec)
    gate = threading.Event()

    def blocking_executor(q):
        gate.wait(30)
        class R:  # minimal QueryResult shape
            column_names, column_types, rows = ["c"], ["bigint"], [[1]]
        return R()

    from presto_tpu.worker.statement import Selector
    rg = ResourceGroupManager(
        [ResourceGroupSpec("tiny", hard_concurrency_limit=1, max_queued=0)],
        selectors=[Selector("tiny")])
    d = DispatchManager(blocking_executor, rg, events=mgr)
    q1 = d.submit("select 1")           # occupies the only slot
    q2 = d.submit("select 2")           # queue full -> rejected
    gate.set()
    assert q2.done.wait(10)
    assert q2.state == "FAILED"
    done = [e for e in rec.completed if e.query_id == q2.query_id]
    assert len(done) == 1 and done[0].state == "FAILED" and done[0].error
    q1.done.wait(10)


def test_trailing_continuation_line(tmp_path):
    p = tmp_path / "c.properties"
    p.write_text("plugin.bundles=/a/b,\\")
    assert load_properties(str(p)) == {"plugin.bundles": "/a/b,"}


def test_literal_lz4_fallback_large_input():
    """The pyarrow-less literal-only LZ4 encoder must produce one
    spec-valid sequence even beyond 1MiB (non-final sequences require a
    match part, so multi-sequence literal-only output is invalid)."""
    from presto_tpu.common.compression import (_lz4_literal_compress,
                                               lz4_block_decompress)
    import os
    data = os.urandom((1 << 20) + 12345)
    packed = _lz4_literal_compress(data)
    assert lz4_block_decompress(packed, len(data)) == data


def test_colon_separator_with_equals_in_value(tmp_path):
    p = tmp_path / "c.properties"
    p.write_text("launcher.args: -Dfoo=bar\n")
    assert load_properties(str(p)) == {"launcher.args": "-Dfoo=bar"}


def test_etc_config_keeps_tuned_defaults(tmp_path):
    """An etc dir with no execution keys must keep the worker's tuned
    ExecutionConfig defaults, not regress to the bare dataclass ones."""
    etc = _write_etc(tmp_path)
    kwargs, _ = server_kwargs_from_etc(etc)
    assert kwargs["config"].batch_rows == 1 << 16
    assert kwargs["config"].join_out_capacity == 1 << 18


# ---------------------------------------------------------------------------
# round 4: typed SystemConfig accessor + worker task-level events
# ---------------------------------------------------------------------------

def test_system_config_typed_accessors():
    from presto_tpu.worker.properties import SystemConfig
    cfg = SystemConfig({"http-server.http.port": "9090",
                        "experimental.spill-enabled": "false",
                        "task.max-drivers-per-task": "8",
                        "node.pool": "LEAF"})
    assert cfg.get("http-server.http.port") == 9090
    assert cfg.get("experimental.spill-enabled") is False
    assert cfg.get("task.max-drivers-per-task") == 8
    assert cfg.get("node.pool") == "LEAF"
    # defaults (Configs.h-style typed defaults) for absent keys
    assert cfg.get("exchange.compression-codec") == "LZ4"
    assert cfg.get("shutdown-onset-sec") == 10
    assert cfg.get("coordinator") is False
    # surface breadth: the most-used Configs.h key set is mapped
    assert len(cfg.known_keys()) >= 40
    import pytest as _pytest
    with _pytest.raises(KeyError):
        cfg.get("no.such.key")
    d = cfg.to_dict()
    assert d["http-server.http.port"] == 9090


def test_announcement_interval_key_mapped(tmp_path):
    etc = _write_etc(tmp_path)
    with open(f"{etc}/config.properties", "a") as f:
        f.write("announcement-interval-ms=250\n")
    kwargs, _ = server_kwargs_from_etc(etc)
    assert kwargs["announce_interval_s"] == 0.25


def test_task_completed_event_fires_from_worker_path():
    """Task-level events come from the WORKER task execution path
    (QueryMonitor.java:106 per-task stats), not only the statement
    protocol: a task run through TaskManager fires task_completed with
    the task's output counters."""
    import base64
    import json as _json
    import time as _time

    from presto_tpu.sql.planner import Planner
    from presto_tpu.spi import plan as P
    from presto_tpu.worker.events import EventListenerManager, EventListener
    from presto_tpu.worker.protocol import (OutputBuffersSpec,
                                            TaskUpdateRequest)
    from presto_tpu.worker.task import TaskManager

    got = []

    class L(EventListener):
        def task_completed(self, event):
            got.append(event)

    events = EventListenerManager()
    events.register(L())
    tm = TaskManager("http://127.0.0.1:0", events=events)
    out = Planner(default_schema="sf0.01", default_catalog="tpch") \
        .plan("SELECT count(*) FROM nation")
    frag = P.PlanFragment(
        "0", out, P.SOURCE_DISTRIBUTION,
        P.PartitioningScheme(P.SINGLE_DISTRIBUTION, [],
                             list(out.output_variables)),
        [n.id for n in P.walk_plan(out)
         if isinstance(n, P.TableScanNode)])
    from presto_tpu.connectors import catalog as cat
    splits = [s.to_dict() for s in cat.make_splits("nation", 0.01, 1)]
    from presto_tpu.worker.protocol import TaskSource
    upd = TaskUpdateRequest.make(
        "evq.0.0.0.0", 0, frag,
        [TaskSource.from_dict({"planNodeId": sid, "splits": splits,
                               "noMoreSplits": True})
         for sid in frag.partitioned_sources],
        OutputBuffersSpec("PARTITIONED", 1))
    tm.create_or_update(upd)
    deadline = _time.time() + 60
    while not got and _time.time() < deadline:
        _time.sleep(0.05)
    assert got, "no task_completed event fired"
    ev = got[0]
    assert ev.task_id == "evq.0.0.0.0"
    assert ev.state == "FINISHED"
    assert ev.output_rows == 1
    assert ev.wall_time_s >= 0
