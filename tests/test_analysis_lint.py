"""Host-sync lint conformance (tier-1): the shipped tree is clean — every
device->host transfer is an acknowledged, pragma'd sync point — and each
hazard shape is detected on a fixture.

The lint is the second prong of the PlanCheck work: plan validation
catches the coordinator inserting a malformed stage; this catches the
executor silently serialising the pipeline with an implicit transfer.
"""
import os
import subprocess
import sys

import pytest

from presto_tpu.analysis.lint import (ALL_LINT_CODES, KERNEL_INTERPRET,
                                      MEM_PRAGMA, MEM_UNCHARGED_STAGING,
                                      NET_NO_TIMEOUT, NET_PRAGMA, PRAGMA,
                                      SYNC_ASARRAY, SYNC_BRANCH, SYNC_CAST,
                                      SYNC_EXPLICIT, SYNC_NETWORK,
                                      SYNC_WALLCLOCK, TELEM_UNBOUNDED_QUEUE,
                                      WALL_PRAGMA, lint_or_raise, lint_paths,
                                      lint_source)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _codes(findings):
    return {f.code for f in findings}


# ---------------------------------------------------------------------------
# the tier-1 gate: shipped tree is clean
# ---------------------------------------------------------------------------

def test_shipped_tree_is_clean():
    findings = lint_paths([os.path.join(REPO, "presto_tpu")])
    assert findings == [], "\n".join(str(f) for f in findings)


def test_module_entry_point_exit_codes(tmp_path):
    """`python -m presto_tpu.analysis.lint` is the CI surface: 0 on the
    shipped tree, nonzero on a traced-.item() fixture."""
    clean = subprocess.run(
        [sys.executable, "-m", "presto_tpu.analysis.lint", "presto_tpu"],
        cwd=REPO, capture_output=True, text=True)
    assert clean.returncode == 0, clean.stdout + clean.stderr

    fixture = tmp_path / "bad.py"
    fixture.write_text(
        "import jax.numpy as jnp\n"
        "def f(x):\n"
        "    return jnp.sum(x).item()\n")
    bad = subprocess.run(
        [sys.executable, "-m", "presto_tpu.analysis.lint", str(fixture)],
        cwd=REPO, capture_output=True, text=True)
    assert bad.returncode == 1
    assert "SYNC001" in bad.stdout


def test_ci_entry_point_exits_clean(tmp_path):
    """`python -m presto_tpu.analysis.ci` is the single gate CI runs:
    lint + concurrency + a PlanChecker sweep, exit 0 on a clean tree and
    a JSON report with the expected shape."""
    import json
    report_path = tmp_path / "report.json"
    proc = subprocess.run(
        [sys.executable, "-m", "presto_tpu.analysis.ci",
         "--max-plans", "3", "--json", str(report_path)],
        cwd=REPO, capture_output=True, text=True)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    report = json.loads(report_path.read_text())
    assert report["clean"] is True
    assert report["total_findings"] == 0
    assert report["files_scanned"] > 0
    assert report["plan_sweep"]["queries"] == 3
    assert report["lint"]["findings"] == []
    assert report["concurrency"]["findings"] == []


# ---------------------------------------------------------------------------
# hazard shapes
# ---------------------------------------------------------------------------

def test_item_call_flagged():
    findings = lint_source(
        "import jax.numpy as jnp\n"
        "def f(x):\n"
        "    s = jnp.sum(x)\n"
        "    return s.item()\n")
    assert _codes(findings) == {SYNC_EXPLICIT}


def test_device_get_flagged():
    findings = lint_source(
        "import jax\n"
        "def f(x):\n"
        "    return jax.device_get(x)\n")
    assert _codes(findings) == {SYNC_EXPLICIT}


def test_block_until_ready_flagged():
    findings = lint_source(
        "def f(x):\n"
        "    return x.block_until_ready()\n")
    assert _codes(findings) == {SYNC_EXPLICIT}


def test_cast_of_traced_value_flagged():
    findings = lint_source(
        "import jax.numpy as jnp\n"
        "def f(x):\n"
        "    return float(jnp.mean(x)), int(jnp.sum(x))\n")
    assert _codes(findings) == {SYNC_CAST}
    assert len(findings) == 2


def test_cast_tracks_assigned_names():
    findings = lint_source(
        "import jax.numpy as jnp\n"
        "def f(x):\n"
        "    total = jnp.sum(x) + 1\n"
        "    return int(total)\n")
    assert _codes(findings) == {SYNC_CAST}


def test_np_asarray_on_device_value_flagged():
    findings = lint_source(
        "import jax.numpy as jnp\n"
        "import numpy as np\n"
        "def f(x):\n"
        "    y = jnp.where(x > 0, x, 0)\n"
        "    return np.asarray(y)\n")
    assert _codes(findings) == {SYNC_ASARRAY}


def test_branch_on_device_bool_flagged():
    findings = lint_source(
        "import jax.numpy as jnp\n"
        "def f(x):\n"
        "    if jnp.any(x > 0):\n"
        "        return 1\n"
        "    while jnp.all(x):\n"
        "        pass\n")
    assert _codes(findings) == {SYNC_BRANCH}
    assert len(findings) == 2


_NET_FIXTURE = ("import urllib.request\n"
                "def fetch(url):\n"
                "    return urllib.request.urlopen(url).read()\n")


def test_network_call_in_compute_module_flagged():
    findings = lint_source(_NET_FIXTURE,
                           path="presto_tpu/exec/bad_net.py")
    assert _codes(findings) == {SYNC_NETWORK}


def test_network_call_outside_compute_paths_not_flagged():
    # worker-layer code (incl. the sanctioned exchange client) may do
    # blocking HTTP; the lint scopes SYNC005 to pipeline compute packages.
    # NET001 still applies there (the fixture omits timeout=) — assert
    # only that SYNC005 stays out of the worker layer.
    for path in ("presto_tpu/worker/exchange.py",
                 "presto_tpu/worker/coordinator.py"):
        assert _codes(lint_source(_NET_FIXTURE, path=path)) == \
            {NET_NO_TIMEOUT}
    assert lint_source(_NET_FIXTURE, path="tools/fetch.py") == []


def test_network_parse_and_error_usage_not_flagged():
    # urllib.parse / urllib.error are metadata, not blocking I/O — they
    # appear legitimately in exec/lowering.py and common/errors.py
    findings = lint_source(
        "from urllib.parse import urlparse\n"
        "import urllib.error\n"
        "def f(u):\n"
        "    try:\n"
        "        return urlparse(u).netloc\n"
        "    except urllib.error.URLError:\n"
        "        return ''\n",
        path="presto_tpu/exec/lowering.py")
    assert findings == []


def test_network_pragma_suppresses():
    findings = lint_source(
        "import urllib.request\n"
        "def fetch(url):\n"
        "    return urllib.request.urlopen(url)  # lint: allow-host-sync\n",
        path="presto_tpu/common/whatever.py")
    assert findings == []


_WALL_FIXTURE = ("import time\n"
                 "def drive(batches):\n"
                 "    t0 = time.perf_counter()\n"
                 "    n = sum(1 for _ in batches)\n"
                 "    return n, time.perf_counter() - t0\n")


def test_wall_clock_in_exec_flagged():
    findings = lint_source(_WALL_FIXTURE,
                           path="presto_tpu/exec/bad_timer.py")
    assert _codes(findings) == {SYNC_WALLCLOCK}
    assert len(findings) == 2


def test_wall_clock_outside_exec_not_flagged():
    # the rule is scoped to the execution layer; worker/bench/storage code
    # times freely
    for path in ("presto_tpu/worker/task.py", "presto_tpu/storage/store.py",
                 "bench.py"):
        assert lint_source(_WALL_FIXTURE, path=path) == []


def test_wall_clock_pragma_suppresses():
    findings = lint_source(
        "import time\n"
        "def drive(stats):\n"
        "    t0 = time.perf_counter()  # lint: allow-wall-clock\n"
        "    stats.record_wall(time.perf_counter() - t0)"
        "  # lint: allow-wall-clock\n",
        path="presto_tpu/exec/scheduler.py")
    assert findings == []


def test_pragmas_are_not_interchangeable():
    # a host-sync acknowledgement must not silence SYNC006 (and vice
    # versa): each code checks only its own pragma's line set
    findings = lint_source(
        "import time\n"
        "def f():\n"
        "    return time.perf_counter()  # lint: allow-host-sync\n",
        path="presto_tpu/exec/whatever.py")
    assert _codes(findings) == {SYNC_WALLCLOCK}
    findings = lint_source(
        "import jax\n"
        "def f(x):\n"
        "    return jax.device_get(x)  # lint: allow-wall-clock\n",
        path="presto_tpu/exec/whatever.py")
    assert _codes(findings) == {SYNC_EXPLICIT}


# ---------------------------------------------------------------------------
# precision: host values and metadata must NOT be flagged
# ---------------------------------------------------------------------------

def test_device_get_result_is_host():
    """device_get moves the value to host: casting/branching on its
    result is the sanctioned pattern, only the device_get itself needs
    the pragma."""
    findings = lint_source(
        "import jax\n"
        "def f(x):\n"
        "    v = jax.device_get(x)  # lint: allow-host-sync\n"
        "    if int(v) > 0:\n"
        "        return float(v)\n")
    assert findings == []


def test_dtype_metadata_is_host():
    findings = lint_source(
        "import jax.numpy as jnp\n"
        "def f(x):\n"
        "    if jnp.issubdtype(x.dtype, jnp.floating):\n"
        "        return int(x.shape[0]) + int(jnp.iinfo(x.dtype).max)\n")
    assert findings == []


def test_plain_python_casts_not_flagged():
    findings = lint_source(
        "def f(args):\n"
        "    return int(args[1].value), float('3')\n")
    assert findings == []


def test_pragma_suppresses():
    findings = lint_source(
        "import jax\n"
        "def f(x):\n"
        "    return bool(jax.device_get(x))  # lint: allow-host-sync\n")
    assert findings == []


def test_pragma_covers_multiline_statement():
    findings = lint_source(
        "import jax\n"
        "def f(x, y):\n"
        "    return jax.device_get(  # lint: allow-host-sync\n"
        "        (x, y))\n")
    assert findings == []


def test_syntax_error_is_reported_not_raised():
    findings = lint_source("def f(:\n")
    assert [f.code for f in findings] == ["SYNTAX"]


def test_lint_routes_through_error_taxonomy(tmp_path):
    """lint_or_raise fails through the same non-retryable PLAN_VALIDATION
    channel as the plan checker."""
    from presto_tpu.common.errors import PlanValidationError, is_retryable
    fixture = tmp_path / "bad.py"
    fixture.write_text("import jax.numpy as jnp\n"
                       "def f(x):\n"
                       "    return jnp.sum(x).item()\n")
    with pytest.raises(PlanValidationError) as ei:
        lint_or_raise([str(fixture)])
    assert ei.value.diagnostics
    assert not is_retryable(ei.value)
    lint_or_raise([os.path.join(REPO, "presto_tpu")])  # clean: no raise


def test_interpret_literal_flagged():
    """KERNEL001: an interpret=True literal would make a TPU build
    silently run a Pallas kernel interpreted."""
    src = ("from jax.experimental import pallas as pl\n"
           "def f(kernel, spec, shapes):\n"
           "    return pl.pallas_call(kernel, grid_spec=spec,\n"
           "                          out_shape=shapes, interpret=True)\n")
    findings = lint_source(src, "presto_tpu/exec/pipeline.py")
    assert KERNEL_INTERPRET in _codes(findings)
    # ...and there is no pragma escape
    src2 = ("from jax.experimental import pallas as pl\n"
            "def f(kernel, spec, shapes):\n"
            "    return pl.pallas_call(\n"
            "        kernel, grid_spec=spec,  # lint: allow-host-sync\n"
            "        out_shape=shapes,\n"
            "        interpret=True)  # lint: allow-wall-clock\n")
    findings = lint_source(src2, "presto_tpu/exec/pipeline.py")
    assert KERNEL_INTERPRET in _codes(findings)


def test_interpret_kwargs_store_flagged():
    findings = lint_source(
        "def f(kwargs):\n"
        "    kwargs['interpret'] = True\n",
        "presto_tpu/exec/pipeline.py")
    assert KERNEL_INTERPRET in _codes(findings)


def test_interpret_has_no_exempt_file():
    """The tree holds no Pallas code, so no file may select interpret
    mode: not a shim-shaped wrapper, and not outside exec/ either."""
    src = ("def pallas_call(kernel, **kwargs):\n"
           "    kwargs['interpret'] = True\n"
           "    return kernel(**kwargs)\n")
    for path in ("presto_tpu/exec/kernels/shim.py",
                 "presto_tpu/serving/batched.py", "bench.py"):
        assert KERNEL_INTERPRET in _codes(lint_source(src, path)), path


def test_exec_package_is_sync_and_wall_scoped():
    """exec/ files fall under the SYNC + wall-clock rules (the path
    markers cover presto_tpu/exec/ recursively)."""
    findings = lint_source(
        "import time\n"
        "import jax.numpy as jnp\n"
        "def f(x):\n"
        "    t0 = time.perf_counter()\n"
        "    return jnp.sum(x).item(), t0\n",
        "presto_tpu/exec/fused.py")
    assert {SYNC_EXPLICIT, SYNC_WALLCLOCK} <= _codes(findings)


@pytest.mark.parametrize("path", [
    "presto_tpu/exec/operators.py",
    "presto_tpu/exec/sub/package.py",
])
def test_exec_files_fall_under_kernel_rules(path):
    """A hand-written kernel that enters exec/ later sits under the
    KERNEL001 + SYNC + wall-clock scope from its first line: an
    interpret literal or a host sync added there must fail tier-1."""
    src = ("from jax.experimental import pallas as pl\n"
           "def f(kernel, shapes):\n"
           "    return pl.pallas_call(kernel, out_shape=shapes,\n"
           "                          interpret=True)\n")
    assert KERNEL_INTERPRET in _codes(lint_source(src, path))
    src2 = ("import time\n"
            "import jax.numpy as jnp\n"
            "def f(x):\n"
            "    t0 = time.perf_counter()\n"
            "    return jnp.sum(x).item(), t0\n")
    assert {SYNC_EXPLICIT, SYNC_WALLCLOCK} <= _codes(
        lint_source(src2, path))


def test_unbounded_queue_in_telemetry_flagged():
    """TELEM001: queue.Queue() with no / zero maxsize and SimpleQueue()
    are unbounded buffers; the telemetry package must bound every
    queue so a stalled sink drops instead of growing until OOM."""
    src = ("import queue\n"
           "a = queue.Queue()\n"
           "b = queue.Queue(maxsize=0)\n"
           "c = queue.SimpleQueue()\n"
           "ok1 = queue.Queue(maxsize=256)\n"
           "ok2 = queue.Queue(128)\n"
           "ok3 = queue.Queue(maxsize=bound)\n")
    findings = lint_source(src, "presto_tpu/telemetry/export.py")
    assert _codes(findings) == {TELEM_UNBOUNDED_QUEUE}
    assert [f.line for f in findings] == [2, 3, 4]


def test_unbounded_queue_outside_telemetry_not_flagged():
    src = "import queue\nq = queue.Queue()\n"
    for path in ("presto_tpu/worker/exchange.py",
                 "presto_tpu/exec/local_exchange.py"):
        assert lint_source(src, path) == []


def test_telemetry_queue_has_no_pragma_escape():
    findings = lint_source(
        "import queue\n"
        "q = queue.Queue()  # lint: allow-host-sync\n",
        "presto_tpu/telemetry/export.py")
    assert _codes(findings) == {TELEM_UNBOUNDED_QUEUE}


def test_telemetry_network_scoping():
    """telemetry/ is network-scoped (SYNC005) except export.py, whose
    OTLP POSTs run on the exporter's background flush thread.  NET001
    (missing timeout=) applies to the whole package, export.py
    included — a flush thread wedged on a dead collector never drains."""
    findings = lint_source(_NET_FIXTURE,
                           path="presto_tpu/telemetry/export.py")
    assert _codes(findings) == {NET_NO_TIMEOUT}
    findings = lint_source(_NET_FIXTURE,
                           path="presto_tpu/telemetry/history.py")
    assert _codes(findings) == {SYNC_NETWORK, NET_NO_TIMEOUT}


def test_urllib_without_timeout_in_worker_flagged():
    """NET001: a urllib request in worker/ or telemetry/ without an
    explicit timeout= can block its thread forever on a dead peer —
    the exact hang the fault-tolerant mode exists to survive."""
    findings = lint_source(_NET_FIXTURE,
                           path="presto_tpu/worker/server.py")
    assert _codes(findings) == {NET_NO_TIMEOUT}
    # urlopen_internal (worker/auth.py wrapper) is held to the same rule
    findings = lint_source(
        "from .auth import urlopen_internal\n"
        "def probe(req):\n"
        "    return urlopen_internal(req)\n",
        path="presto_tpu/worker/coordinator.py")
    assert _codes(findings) == {NET_NO_TIMEOUT}


def test_urllib_with_timeout_not_flagged():
    src = ("import urllib.request\n"
           "def fetch(url):\n"
           "    return urllib.request.urlopen(url, timeout=5).read()\n")
    assert lint_source(src, path="presto_tpu/worker/server.py") == []
    # a **kwargs splat is trusted to carry the caller's bound
    src2 = ("import urllib.request\n"
            "def fetch(url, **kw):\n"
            "    return urllib.request.urlopen(url, **kw).read()\n")
    assert lint_source(src2, path="presto_tpu/worker/server.py") == []


def test_urllib_timeout_scope_and_pragma():
    # the rule is scoped to worker/ + telemetry/; elsewhere urllib calls
    # answer only to SYNC005's compute-module scoping
    assert lint_source(_NET_FIXTURE, path="presto_tpu/sql/planner.py") == []
    suppressed = lint_source(
        "import urllib.request\n"
        "def fetch(url):\n"
        "    return urllib.request.urlopen(url)  # lint: allow-no-timeout\n",
        path="presto_tpu/worker/server.py")
    assert suppressed == []
    # ...and the net pragma is its own line set: a host-sync pragma does
    # not silence NET001
    findings = lint_source(
        "import urllib.request\n"
        "def fetch(url):\n"
        "    return urllib.request.urlopen(url)  # lint: allow-host-sync\n",
        path="presto_tpu/worker/server.py")
    assert _codes(findings) == {NET_NO_TIMEOUT}


_MEM_FIXTURE = ("class BucketStager:\n"
                "    def __init__(self):\n"
                "        self.pending_pages = []\n"
                "        self._chunks: dict = {}\n"
                "    def add(self, b):\n"
                "        self.pending_pages.append(b)\n")


def test_uncharged_staging_class_flagged():
    """MEM001: a class in exec//worker/ that stages rows in unbounded
    host collections but never touches the memory-accounting API is
    invisible to the arbitrator — exactly the PR 2 retained-buffer
    leak this rule fossilizes."""
    findings = lint_source(_MEM_FIXTURE, path="presto_tpu/exec/stager.py")
    assert _codes(findings) == {MEM_UNCHARGED_STAGING}
    assert [f.line for f in findings] == [3, 4]
    findings = lint_source(_MEM_FIXTURE, path="presto_tpu/worker/stager.py")
    assert _codes(findings) == {MEM_UNCHARGED_STAGING}


def test_charged_staging_class_not_flagged():
    # any reference to the charging API in the class body vouches for it
    src = _MEM_FIXTURE.replace(
        "    def add(self, b):\n",
        "    def add(self, b, ctx):\n"
        "        ctx.try_reserve(len(b))\n")
    assert lint_source(src, path="presto_tpu/exec/stager.py") == []
    src2 = _MEM_FIXTURE.replace(
        "    def add(self, b):\n",
        "    def attach(self, memory_context):\n"
        "        self.memory_context = memory_context\n"
        "    def add(self, b):\n")
    assert lint_source(src2, path="presto_tpu/worker/stager.py") == []


def test_staging_outside_memory_scope_not_flagged():
    # the rule is scoped to exec/ and worker/; sql- and storage-layer
    # collections hold plans and metadata, not row data
    for path in ("presto_tpu/sql/planner.py",
                 "presto_tpu/storage/store.py", "bench.py"):
        assert lint_source(_MEM_FIXTURE, path=path) == []


def test_bounded_and_copy_constructors_not_flagged():
    src = ("import collections\n"
           "class RingStager:\n"
           "    def __init__(self, pages):\n"
           "        self.pending_pages = collections.deque(maxlen=8)\n"
           "        self.page_copy = list(pages)\n")
    assert lint_source(src, path="presto_tpu/exec/ring.py") == []


def test_uncharged_staging_pragma_suppresses():
    src = _MEM_FIXTURE.replace(
        "self.pending_pages = []",
        "self.pending_pages = []  # lint: allow-uncharged-staging").replace(
        "self._chunks: dict = {}",
        "self._chunks: dict = {}  # lint: allow-uncharged-staging")
    assert lint_source(src, path="presto_tpu/exec/stager.py") == []
    # ...but the memory pragma is its own line set: a host-sync pragma
    # does not silence MEM001
    src2 = _MEM_FIXTURE.replace(
        "self.pending_pages = []",
        "self.pending_pages = []  # lint: allow-host-sync")
    findings = lint_source(src2, path="presto_tpu/exec/stager.py")
    assert MEM_UNCHARGED_STAGING in _codes(findings)


def test_all_codes_are_exercised_above():
    assert set(ALL_LINT_CODES) == {SYNC_EXPLICIT, SYNC_CAST, SYNC_ASARRAY,
                                   SYNC_BRANCH, SYNC_NETWORK, SYNC_WALLCLOCK,
                                   KERNEL_INTERPRET, TELEM_UNBOUNDED_QUEUE,
                                   MEM_UNCHARGED_STAGING, NET_NO_TIMEOUT}
    assert PRAGMA == "lint: allow-host-sync"
    assert WALL_PRAGMA == "lint: allow-wall-clock"
    assert MEM_PRAGMA == "lint: allow-uncharged-staging"
    assert NET_PRAGMA == "lint: allow-no-timeout"
