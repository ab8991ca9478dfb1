"""The readers of the program's own spans and counters, on hand-made
windows: the value per job, and None wherever there is nothing to read."""
from __future__ import annotations

import sys
import types

import pytest

from bench import run
from bench.jobs import Record
from bench_tiny import REPO

S = 1_000_000_000  # ns in a second


def _reader(name):
    return run.Bench(REPO).reader(name)


def _obs(kind, records, spans=(), instants=(), jobs=()):
    return dict(kind=kind, records=list(records), spans={}, lower_bytes=None, peaks={},
                trace={"jobs": list(jobs), "busy_s": 0.0, "window_s": 1.0},
                program={"spans": list(spans), "instants": list(instants)})


def _partition_window():
    """Two partition jobs of 10 s each, the host phases inside each, and
    spans outside every job (warm-up, check) that no job may count."""
    records = [Record(0, 10 * S, None), Record(10 * S, 20 * S, None)]
    spans = [("partition.order", -5 * S, -2 * S, 1, 0)]  # before the window
    for i, (t, order_s) in enumerate(((0, 2.0), (10 * S, 3.0))):
        root = 10 * (i + 1)
        spans += [
            ("partition.validate", t + S // 10, t + S // 2, root + 1, root),
            ("partition.order", t + S // 2, t + S // 2 + int(order_s * S), root + 2, root),
            ("partition.upload", t + 4 * S, t + 4 * S + S // 4, root + 3, root),
            ("partition.commit", t + 5 * S, t + 5 * S + S // 100, root + 4, root),
            ("partition.run", t, t + 6 * S, root, 0),
            ("partition.fetch", t + 6 * S, t + 9 * S, root + 5, 0),
        ]
    spans.append(("partition.order", 21 * S, 30 * S, 99, 0))  # after the window
    return _obs("partition", records, spans)


def test_partition_order_s():
    assert _reader("partition_order_s")(_partition_window()) == pytest.approx(2.5)


def test_partition_prep_s():
    # validate 0.4 s + order 2 or 3 s + upload 0.25 s a job
    assert _reader("partition_prep_s")(_partition_window()) == pytest.approx(0.4 + 2.5 + 0.25)


def _pr_window():
    records = [Record(0, 2 * S, None), Record(2 * S, 4 * S, None), Record(4 * S, 6 * S, None)]
    spans, instants = [], []
    for i, dispatch_ms in enumerate((200, 250, 300)):
        t = 2 * S * i
        spans += [("engine.prepare", t + 1_000, t + 2_000, 3 * i + 2, 3 * i + 1),
                  ("engine.dispatch", t + S // 10, t + S // 10 + dispatch_ms * 1_000_000,
                   3 * i + 3, 3 * i + 1),
                  ("engine.run", t, t + 2 * S - 1, 3 * i + 1, 0)]
        instants.append(("engine.trace", t + S // 5, 1))
        instants.append(("engine.dispatch.fused", t + S, 1))
    instants.append(("engine.trace", -S, 1))  # the warm-up's trace, outside the window
    return _obs("engine", records, spans, instants)


def test_engine_dispatch_s_pr():
    assert _reader("engine_dispatch_s.pr")(_pr_window()) == pytest.approx(0.25)


def test_engine_traces_pr():
    assert _reader("engine_traces.pr")(_pr_window()) == 1.0
    obs = _pr_window()
    obs["program"]["instants"] = [i for i in obs["program"]["instants"] if i[0] != "engine.trace"]
    assert _reader("engine_traces.pr")(obs) == 0.0  # a cached stepper reads 0, not None
    obs["program"]["instants"].append(("engine.trace", 3 * S, 3))
    assert _reader("engine_traces.pr")(obs) == 1.0


def _bfs_window(passes=(20, 25)):
    records = [Record(0, 12 * S, None, types.SimpleNamespace(relax_passes=p)) for p in passes]
    jobs = [{"busy_s": [11.0], "span_s": 12.0}, {"busy_s": [12.5], "span_s": 12.6}]
    return _obs("engine", records, jobs=jobs)


def test_engine_pass_s():
    assert _reader("engine_pass_s")(_bfs_window()) == pytest.approx((11.0 / 20 + 12.5 / 25) / 2)
    four = _bfs_window()
    four["trace"]["jobs"] = [{"busy_s": [1.0, 3.0, 2.0, 0.5], "span_s": 4.0}] * 2
    assert _reader("engine_pass_s")(four) == pytest.approx((3.0 / 20 + 3.0 / 25) / 2)


def test_engine_pass_s_is_silent_where_jobs_do_not_pair():
    read = _reader("engine_pass_s")
    short = _bfs_window()
    short["trace"]["jobs"] = short["trace"]["jobs"][:1]
    assert read(short) is None
    assert read(_bfs_window(passes=(20, 0))) is None
    no_count = _bfs_window()
    no_count["records"][0].stats = types.SimpleNamespace(supersteps=5)  # a program without it
    assert read(no_count) is None


@pytest.mark.parametrize("metric,window", [
    ("partition_order_s", _partition_window), ("partition_prep_s", _partition_window),
    ("engine_dispatch_s.pr", _pr_window), ("engine_traces.pr", _pr_window),
    ("engine_pass_s", _bfs_window)])
def test_reader_is_silent_off_its_kind_and_on_an_empty_window(metric, window):
    obs = window()
    read = _reader(metric)
    assert read(obs) is not None
    assert read(dict(obs, kind="partition" if obs["kind"] == "engine" else "engine")) is None
    assert read(dict(obs, records=[], trace=dict(obs["trace"], jobs=[]))) is None


def test_span_readers_are_silent_where_a_job_has_no_span():
    obs = _partition_window()
    obs["program"]["spans"] = [s for s in obs["program"]["spans"]
                               if not (s[0] == "partition.order" and s[1] >= 10 * S)]
    assert _reader("partition_order_s")(obs) is None


@pytest.mark.parametrize("metric", ["partition_order_s", "partition_prep_s",
                                    "engine_dispatch_s.pr", "engine_traces.pr"])
def test_reader_is_silent_on_a_program_without_spans(monkeypatch, metric):
    """A program without `repro.obs` (an older checkout) has nothing to read:
    the reader returns None and does not raise."""
    monkeypatch.setitem(sys.modules, "repro.obs", None)  # import raises ImportError
    obs = _partition_window() if metric.startswith("partition") else _pr_window()
    del obs["program"]
    assert _reader(metric)(obs) is None


def test_readers_read_the_program_in_this_process():
    """Without `obs["program"]`, the readers read `repro.obs` itself."""
    from repro import obs as program_obs

    program_obs.clear()
    try:
        with program_obs.recording():
            with program_obs.span("engine.dispatch"):
                program_obs.count("engine.trace")
        (s,) = program_obs.spans()
        obs = _obs("engine", [Record(s.t0_ns - 10, s.t1_ns + 10, None)])
        del obs["program"]
        assert _reader("engine_dispatch_s.pr")(obs) == pytest.approx((s.t1_ns - s.t0_ns) / 1e9)
        assert _reader("engine_traces.pr")(obs) == 1.0
    finally:
        program_obs.clear()
