"""ISSUE 6 satellite: the perf guard must survive a mangled history.

``BENCH_history.jsonl`` is append-only and crash-prone (a killed bench
run leaves a truncated last line; caches merge files from other hosts),
so ``read_history`` skips corrupt / truncated / non-object lines with a
warning instead of crashing, and ``check_regression.check`` ignores
non-numeric metric values in baseline rows.  A missing or empty file is
simply "no history" — the guard passes, it never blocks a fresh host.
A genuine >30% drop between comparable rows must still exit 1.
"""
import json
import os
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                "benchmarks"))
import check_regression  # noqa: E402
import run as bench_run  # noqa: E402


def _row(pps_1dev, pps_8dev=2e6, **over):
    row = {"schema": bench_run.HISTORY_SCHEMA, "bench": "mega_sweep",
           "mega_n_points": 12_600_000, "devices": [1, 8], "cpus": 2,
           "git_sha": "abc123", "mega_points_per_sec_1dev": pps_1dev,
           "mega_points_per_sec_8dev": pps_8dev}
    row.update(over)
    return row


@pytest.fixture()
def history(tmp_path, monkeypatch):
    path = tmp_path / "BENCH_history.jsonl"
    monkeypatch.setattr(bench_run, "HISTORY", str(path))
    return path


def _write(path, *lines):
    path.write_text("".join(
        (line if isinstance(line, str) else json.dumps(line)) + "\n"
        for line in lines))


def test_absent_and_empty_history_pass(history, capsys):
    assert check_regression.check() == 0          # file doesn't exist
    assert "no mega_sweep rows" in capsys.readouterr().out
    history.write_text("")
    assert check_regression.check() == 0          # file exists, empty
    assert bench_run.read_history() == []


def test_corrupt_lines_skipped_with_warning(history, capsys):
    _write(history,
           _row(1e6),
           '{"schema": 1, "bench": "mega_sweep", "mega_points_',  # truncated
           "not json at all {{{",
           '["a", "list", "row"]',                                # non-object
           _row(1e6))
    rows = bench_run.read_history("mega_sweep")
    assert len(rows) == 2, "valid rows must survive the mangled ones"
    err = capsys.readouterr().err
    assert err.count("malformed history line") == 2
    assert err.count("non-object history row") == 1
    # the guard sees identical throughput -> PASS, no crash
    assert check_regression.check() == 0


def test_truncated_last_line_does_not_crash(history):
    full = json.dumps(_row(1e6))
    history.write_text(full + "\n" + full[: len(full) // 2])
    assert bench_run.read_history("mega_sweep") == [json.loads(full)]
    assert check_regression.check() == 0


def test_non_numeric_baseline_metric_ignored(history, capsys):
    _write(history,
           _row("fast"),                     # corrupt baseline value
           _row(True),                       # bool is not a throughput
           _row(1e6),
           _row(1e6))
    assert check_regression.check() == 0
    out = capsys.readouterr().out
    assert "ignoring 2 baseline row(s) with non-numeric " \
           "mega_points_per_sec_1dev" in out


def test_non_numeric_current_metric_skipped(history, capsys):
    _write(history, _row(1e6), _row(None))
    assert check_regression.check() == 0
    assert "missing or non-numeric" in capsys.readouterr().out


def test_genuine_regression_still_fails(history, capsys):
    _write(history, _row(1e6), _row(1e6), _row(0.6e6))   # -40% drop
    assert check_regression.check() == 1
    assert "REGRESSION" in capsys.readouterr().out


def test_incomparable_rows_never_baseline(history, capsys):
    # different host / grid rows must not poison the comparison
    _write(history,
           _row(9e6, cpus=64),
           _row(9e6, mega_n_points=100),
           _row(1e6),
           _row(1e6))
    assert check_regression.check() == 0
    assert "PASS" in capsys.readouterr().out


def test_campaign_rows_invisible_to_mega_guard(history):
    # the campaign bench appends bench="campaign_sweep" rows; the guard
    # filters on bench, so they can never become a mega baseline
    _write(history, _row(1e6, bench="campaign_sweep"), _row(1e6))
    assert [r["bench"] for r in bench_run.read_history("mega_sweep")] \
        == ["mega_sweep"]
    assert check_regression.check() == 0


def test_main_exits_nonzero_when_a_bench_raised(monkeypatch, capsys):
    """A raising bench prints its ERROR row, the others still run, and
    the process then exits non-zero instead of reporting success."""
    def good():
        return ["good,1,ok"]

    def bad():
        raise RuntimeError("refused by the compiler")

    monkeypatch.setattr(bench_run, "BENCHES", [bad, good])
    monkeypatch.setattr(bench_run, "setup_compile_cache", lambda: None)
    with pytest.raises(SystemExit) as exc:
        bench_run.main([])
    assert exc.value.code not in (0, None)
    out = capsys.readouterr().out
    assert "bad,0,ERROR RuntimeError: refused by the compiler" in out
    assert "good,1,ok" in out
