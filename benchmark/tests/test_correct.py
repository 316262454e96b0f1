"""What decides `correct`, shown to fail.

The control: the plain reference with one guarantee of the configuration
broken (the mix's `check.control`: float32 sums in place of exact
decimals, or a scan that stops a batch short of the table's end), put in
the program's place -- it has to come out not correct.  The faults: the harness drives the rest of a run at sf0.01
on the CPU (the look for a chip is skipped) with the timed path broken
underneath, and has to see `correct` come out false, once for each fault
a cell can have:

  half_rows      every second scan split handed to no task (half of the
                 table left out, the aggregates taken over the rest)
  no_exchange    the pages of every second producer task never pulled by
                 the stage above (the exchange between stages left out)
  altered        one value of one answer altered where the server
                 produces it
  swapped_lanes  two lanes of a batched launch handed each other's rows
"""
import contextlib
import io

import pytest

import check
from cells import Cell, Query

SF = 0.01
CELLS = ["tpch10-cluster.scan-power", "tpch10-single.dash-8c",
         "tpch10-cluster.join-power"]


def drive(workload, seed=5, seconds=2.0, control=0, trace=0):
    """run.py's run_cell on the CPU at a tiny scale factor."""
    import jax
    import run
    cell = Cell(workload)
    config = dict(cell.config, scale_factor=SF)
    with contextlib.redirect_stdout(io.StringIO()):
        run.OUT = io.StringIO()
        result = run.run_cell(cell, config, seed, seconds, trace, control,
                              jax.devices()[:1], None)
    return result


def numbers(result):
    return {k: v["value"] for k, v in result["compared"].items()}


@pytest.mark.parametrize("workload", CELLS)
def test_sound_run_compares_answers_and_finds_none_wrong(workload):
    result = drive(workload)
    n = numbers(result)
    assert result["correct"] is True
    assert n["answers_compared"] >= 1
    assert n["answers_wrong"] == 0 and n["requests_failed"] == 0


@pytest.mark.parametrize("workload", CELLS)
def test_control_comes_out_not_correct(workload):
    cell = Cell(workload)
    # a size at which the last 1/1024 of lineitem holds rows of the joins
    reference = check.Reference(cell.queries, 10 * SF)
    import load
    plan = load.Plan(cell.traffic, cell.queries, 7)
    requests = []
    for i in range(8):
        t, v, _sql = plan.request(0, i)
        requests.append({"template": t, "values": v, "ok": True,
                         "rows": reference.answer(t, v), "wall_s": 1.0})
    sound = check.compare(requests, requests, reference)
    assert sound["correct"] and sound["numbers"]["answers_wrong"]["value"] == 0
    control = check.compare(requests, requests, reference,
                            control=cell.traffic["check"]["control"])
    assert not control["correct"]
    assert control["numbers"]["answers_wrong"]["value"] >= 1


def test_a_request_that_never_answers_is_not_correct():
    reference = check.Reference({"tpch/q6": Query("tpch/q6")}, SF)
    lost = {"template": "tpch/q6", "values": {}, "ok": False, "rows": None,
            "wall_s": 1.0}
    assert not check.compare([lost], [], reference)["correct"]


# ---- the timed path, broken underneath -----------------------------------

@contextlib.contextmanager
def half_rows():
    from presto_tpu.worker import coordinator as C
    real = C._QueryExecution._make_sources

    def broken(self, stage, ti):
        sources = real(self, stage, ti)
        if ti % 2 == 1:
            for s in sources:
                if s.splits and not s.splits[0].get("remote"):
                    s.splits[:] = []
        return sources
    C._QueryExecution._make_sources = broken
    try:
        yield
    finally:
        C._QueryExecution._make_sources = real


@contextlib.contextmanager
def no_exchange():
    from presto_tpu.worker import coordinator as C
    real = C._QueryExecution._make_sources

    def broken(self, stage, ti):
        sources = real(self, stage, ti)
        for s in sources:
            if len(s.splits) > 1 and s.splits[0].get("remote"):
                s.splits[:] = s.splits[::2]
        return sources
    C._QueryExecution._make_sources = broken
    try:
        yield
    finally:
        C._QueryExecution._make_sources = real


def _alter(rows):
    rows = [list(r) for r in rows]
    for r in rows:
        for i, v in enumerate(r):
            if v is not None and not isinstance(v, str):
                r[i] = v + 1
                return rows
    return rows


@contextlib.contextmanager
def altered():
    from presto_tpu.worker import server as S
    from presto_tpu.worker.statement import StreamingResult
    real = S.WorkerServer._execute_statement

    def broken(self, q):
        result = real(self, q)
        if not q.sql.lstrip().lower().startswith(("select", "execute")):
            return result
        if isinstance(result, StreamingResult):
            return StreamingResult(result.columns,
                                   iter(_alter(list(result.row_iter))),
                                   result.stats)
        result.rows = _alter(result.rows)
        return result
    S.WorkerServer._execute_statement = broken
    try:
        yield
    finally:
        S.WorkerServer._execute_statement = real


@contextlib.contextmanager
def swapped_lanes():
    from presto_tpu.exec import runner as R
    real = R.LocalQueryRunner.execute_prepared_batch

    def broken(self, stmts, prepared=None):
        out = real(self, stmts, prepared=prepared)
        if out and len(out) > 1 and out[0] is not None and out[1] is not None:
            out[0], out[1] = out[1], out[0]
        return out
    import load
    stagger, load.START_STAGGER_S = load.START_STAGGER_S, 0.0
    R.LocalQueryRunner.execute_prepared_batch = broken
    try:        # clients that start together, so that batches do form
        yield
    finally:
        R.LocalQueryRunner.execute_prepared_batch = real
        load.START_STAGGER_S = stagger


FAULTS = [("tpch10-cluster.scan-power", half_rows),
          ("tpch10-cluster.scan-power", no_exchange),
          ("tpch10-cluster.scan-power", altered),
          ("tpch10-cluster.join-power", half_rows),
          ("tpch10-cluster.join-power", no_exchange),
          ("tpch10-single.dash-8c", altered),
          ("tpch10-single.dash-8c", swapped_lanes)]


@pytest.mark.parametrize("workload,fault", FAULTS,
                         ids=[f"{w}-{f.__name__}" for w, f in FAULTS])
def test_fault_in_the_timed_path_comes_out_not_correct(workload, fault):
    with fault():
        try:
            result = drive(workload)
        except RuntimeError as e:        # the warm-up may already fail
            pytest.skip(f"the fault stopped set-up: {e}")
    n = numbers(result)
    assert result["correct"] is False
    assert n["answers_wrong"] >= 1 or n["requests_failed"] >= 1, n
