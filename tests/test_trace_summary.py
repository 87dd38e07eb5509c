"""benchmarks/trace_summary.py: the profiler-trace reduction behind the
device busy share and per-op times in PERF.md."""
import gzip
import json

import pytest

from benchmarks.trace_summary import busy_ms, main, summarize


def _meta(pid, tid, proc, thread):
    return [{"ph": "M", "name": "process_name", "pid": pid,
             "args": {"name": proc}},
            {"ph": "M", "name": "thread_name", "pid": pid, "tid": tid,
             "args": {"name": thread}}]


def _op(pid, tid, name, ts, dur, source=None):
    e = {"ph": "X", "pid": pid, "tid": tid, "name": name, "ts": ts,
         "dur": dur}
    if source:
        e["args"] = {"source": source}
    return e


EVENTS = (_meta(3, 3, "/device:TPU:0", "XLA Ops")
          + _meta(7, 1, "/host:CPU", "python")
          + _meta(7, 2, "/host:CPU", "pjrt-tpu-tasks")
          + [_op(3, 3, "fusion", 0, 400, "ops.py:198"),
             _op(3, 3, "kernel", 300, 200),      # overlaps the fusion
             _op(3, 3, "fusion", 900, 100, "ops.py:198"),
             _op(3, 3, "fusion", 950, 10, "rnn.py:36"),  # another module's
             _op(7, 1, "serve_multi", 0, 2000),
             _op(7, 2, "copy", 0, 50)])          # neither device nor python


@pytest.mark.parametrize("intervals,want", [
    ([], 0.0),
    ([(0, 1000)], 1.0),
    ([(0, 400), (300, 500), (900, 1000)], 0.6),   # overlap counted once
    ([(900, 1000), (0, 2000)], 2.0),              # nested, unsorted
])
def test_busy_ms_is_the_union_of_intervals(intervals, want):
    assert busy_ms(intervals) == pytest.approx(want)


def test_summarize_device_busy_share_and_top_ops():
    s = summarize(EVENTS)
    assert set(s) == {"/device:TPU:0 XLA Ops", "/host:CPU python"}
    dev = s["/device:TPU:0 XLA Ops"]
    assert dev["busy_ms"] == pytest.approx(0.6)
    assert dev["span_ms"] == pytest.approx(1.0)
    assert dev["n"] == 4
    assert dev["top"] == [("fusion", pytest.approx(0.5), 2, "ops.py:198"),
                          ("kernel", pytest.approx(0.2), 1, ""),
                          ("fusion", pytest.approx(0.01), 1, "rnn.py:36")]
    assert s["/host:CPU python"]["busy_ms"] == pytest.approx(2.0)


def test_cli_reads_a_gzipped_trace(tmp_path, capsys):
    path = tmp_path / "run.trace.json.gz"
    with gzip.open(path, "wt") as f:
        json.dump({"traceEvents": EVENTS}, f)
    main([str(path), "--top", "1"])
    out = capsys.readouterr().out
    assert ("/device:TPU:0 XLA Ops: 4 events, busy 0.600 ms over span "
            "1.000 ms") in out
    assert "fusion  (ops.py:198)" in out
    assert "kernel" not in out                    # cut by --top 1
