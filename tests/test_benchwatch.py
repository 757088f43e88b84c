"""tools/benchwatch.py: the BENCH-trajectory regression watch (ISSUE 7).

Rehearsal-scale: synthetic BENCH_r*.json archives exercise the delta
table, the like-for-like predecessor rule, missing-key tolerance and the
exit-code contract; one test runs the watch over the real committed
trajectory to prove the tool parses every round the repo actually ships.
"""

import importlib.util
import json
import os

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def benchwatch():
    spec = importlib.util.spec_from_file_location(
        "benchwatch", os.path.join(_REPO, "tools", "benchwatch.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _round(tmp_path, n, parsed):
    with open(tmp_path / f"BENCH_r{n:02d}.json", "w") as f:
        json.dump({"n": n, "cmd": "bench", "rc": 0, "parsed": parsed}, f)


def _parsed(value, metric="sd14_imgs_per_s", **extra):
    return {"metric": metric, "value": value, "unit": "img/s/chip", **extra}


def test_improving_trajectory_passes(benchwatch, tmp_path):
    _round(tmp_path, 1, _parsed(0.5, serve={"p95_ms": 900.0},
                                obs={"overhead_pct": 20.0}))
    _round(tmp_path, 2, _parsed(0.6, serve={"p95_ms": 800.0},
                                obs={"overhead_pct": 18.0}))
    report = benchwatch.watch(str(tmp_path), 0.10)
    assert report["comparable"]
    assert report["latest_round"] == 2 and report["prev_round"] == 1
    assert not report["regressions"]
    by = {r["key"]: r for r in report["rows"]}
    assert by["value"]["status"] == "improved"
    assert by["serve.p95_ms"]["status"] == "improved"
    assert by["phase1_ms_per_step"]["status"] == "n/a"   # absent both sides
    assert benchwatch.main(["--root", str(tmp_path)]) == 0


def test_regression_past_threshold_fails(benchwatch, tmp_path, capsys):
    _round(tmp_path, 1, _parsed(1.0, serve={"p95_ms": 500.0}))
    _round(tmp_path, 2, _parsed(0.8, serve={"p95_ms": 520.0}))   # -20% value
    report = benchwatch.watch(str(tmp_path), 0.10)
    assert [r["key"] for r in report["regressions"]] == ["value"]
    # p95 grew 4%: inside the 10% budget.
    by = {r["key"]: r for r in report["rows"]}
    assert by["serve.p95_ms"]["status"] == "ok"
    assert benchwatch.main(["--root", str(tmp_path)]) == 1
    out = capsys.readouterr().out
    assert "BENCH TREND REGRESSION: value" in out
    # A looser budget passes the same data.
    assert benchwatch.main(["--root", str(tmp_path),
                            "--threshold", "0.25"]) == 0


def test_lower_is_better_direction(benchwatch, tmp_path):
    _round(tmp_path, 1, _parsed(1.0, obs={"overhead_pct": 10.0},
                                phase2_ms_per_step=20.0))
    _round(tmp_path, 2, _parsed(1.0, obs={"overhead_pct": 25.0},
                                phase2_ms_per_step=15.0))
    report = benchwatch.watch(str(tmp_path), 0.10)
    by = {r["key"]: r for r in report["rows"]}
    assert by["obs.overhead_pct"]["status"] == "REGRESSION"   # grew 150%
    assert by["phase2_ms_per_step"]["status"] == "improved"   # dropped


def test_slo_premium_p99_ratio_direction(benchwatch, tmp_path):
    """ISSUE 12 rehearsal: serve.slo.premium_p99_ratio is a headline key
    watched direction-aware (lower is better) — a round where premium p99
    drifts past its uncontended baseline by more than the threshold fails
    the watch, and an improving ratio reads as improved."""
    _round(tmp_path, 1, _parsed(1.0, serve={"slo": {
        "premium_p99_ratio": 1.0}}))
    _round(tmp_path, 2, _parsed(1.0, serve={"slo": {
        "premium_p99_ratio": 1.3}}))   # +30% the wrong way
    report = benchwatch.watch(str(tmp_path), 0.10)
    by = {r["key"]: r for r in report["rows"]}
    assert by["serve.slo.premium_p99_ratio"]["status"] == "REGRESSION"
    assert [r["key"] for r in report["regressions"]] == [
        "serve.slo.premium_p99_ratio"]
    assert benchwatch.main(["--root", str(tmp_path)]) == 1
    _round(tmp_path, 3, _parsed(1.0, serve={"slo": {
        "premium_p99_ratio": 0.99}}))
    report = benchwatch.watch(str(tmp_path), 0.10)
    by = {r["key"]: r for r in report["rows"]}
    assert by["serve.slo.premium_p99_ratio"]["status"] == "improved"
    assert not report["regressions"]


def test_metric_change_is_not_comparable(benchwatch, tmp_path):
    """An on-chip round after tiny-CPU rounds must not diff a preset change as a regression."""
    _round(tmp_path, 1, _parsed(12.5, metric="tiny_cpu_smoke"))
    _round(tmp_path, 2, _parsed(0.96, metric="sd14_imgs_per_s"))
    report = benchwatch.watch(str(tmp_path), 0.10)
    assert not report["comparable"]
    assert "no earlier round" in report["note"]
    assert benchwatch.main(["--root", str(tmp_path)]) == 0
    # ...but a LATER same-metric round skips past the foreign one.
    _round(tmp_path, 3, _parsed(0.90, metric="sd14_imgs_per_s"))
    report = benchwatch.watch(str(tmp_path), 0.02)
    assert report["comparable"] and report["prev_round"] == 2
    assert [r["key"] for r in report["regressions"]] == ["value"]


def test_unparsed_rounds_are_skipped(benchwatch, tmp_path):
    with open(tmp_path / "BENCH_r01.json", "w") as f:
        json.dump({"n": 1, "rc": 1, "parsed": None}, f)   # r01's shape
    _round(tmp_path, 2, _parsed(1.0))
    _round(tmp_path, 3, _parsed(1.05))
    report = benchwatch.watch(str(tmp_path), 0.10)
    assert report["comparable"]
    assert report["prev_round"] == 2


def test_empty_archive_is_an_explicit_note_and_exit_0(benchwatch, tmp_path,
                                                      capsys):
    """An empty bench trajectory (no BENCH_r*.json at all — the empty
    ``bench_runs`` shape) is an explicit "no comparable round" note and
    exit 0, not a silently-green table of per-key n/a rows."""
    report = benchwatch.watch(str(tmp_path), 0.10)
    assert not report["comparable"] and not report["regressions"]
    assert report["rows"] == []
    assert "no comparable round" in report["note"]
    assert benchwatch.main(["--root", str(tmp_path)]) == 0
    assert "no comparable round" in capsys.readouterr().out


def test_single_round_archive_is_an_explicit_note_and_exit_0(benchwatch,
                                                             tmp_path,
                                                             capsys):
    # One round = nothing like-for-like to diff: same explicit-note
    # contract as the empty archive, naming the round that lacks a twin.
    _round(tmp_path, 1, _parsed(1.0, serve={"p95_ms": 500.0}))
    report = benchwatch.watch(str(tmp_path), 0.10)
    assert not report["comparable"] and report["rows"] == []
    assert "no comparable round" in report["note"]
    assert report["latest_round"] == 1
    assert benchwatch.main(["--root", str(tmp_path)]) == 0
    assert "no comparable round" in capsys.readouterr().out


def test_dotted_lookup(benchwatch):
    parsed = {"a": {"b": {"c": 3}}, "x": 1.5, "s": "str", "t": True}
    assert benchwatch.lookup(parsed, "a.b.c") == 3.0
    assert benchwatch.lookup(parsed, "x") == 1.5
    assert benchwatch.lookup(parsed, "a.b.missing") is None
    assert benchwatch.lookup(parsed, "s") is None
    assert benchwatch.lookup(parsed, "t") is None   # bools are not metrics


def test_default_root_holds_no_archive(benchwatch, capsys):
    """The repo commits no bench rounds: the default root is an empty
    archive, which is the explicit "no comparable round" note and exit 0."""
    assert benchwatch.load_rounds(_REPO) == []
    report = benchwatch.watch(_REPO, 0.10)
    assert not report["comparable"]
    benchwatch.render(report)        # never raises
    assert benchwatch.main([]) == 0
