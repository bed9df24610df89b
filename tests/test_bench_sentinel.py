"""Bench-history regression sentinel (ISSUE 8): noise-aware baseline
comparison over the checked-in BENCH/SERVE/MULTICHIP round series.

Contracts under test (incl. the acceptance criterion):
  * a clean history of round files passes clean;
  * an artificial 20% tokens/sec regression appended to that history IS
    flagged, and the ``--smoke`` CI gate verifies both at once;
  * direction-awareness: latency regresses UP, throughput DOWN,
    improvements never flag; contract metrics (decode compile count,
    dryrun ok) flag on ANY change;
  * noise-awareness: a jittery history widens tolerance (within the cap),
    a flat history is held tight;
  * ranked output (worst regression first) and exit codes.

Stdlib-only module under test — imported straight from tools/.
"""
import json
import os
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "tools"))
import bench_sentinel  # noqa: E402



@pytest.fixture
def history(tmp_path):
    """A synthetic round history in the repo-root file formats. The tests
    own their records: the repo root's come and go with the benchmark."""
    def write(name, doc):
        (tmp_path / name).write_text(json.dumps(doc))

    for r, (tok, mfu) in enumerate(
            [(118000.0, 0.512), (121000.0, 0.525), (121300.0, 0.526),
             (120900.0, 0.5246)], start=1):
        write(f"BENCH_r{r:02d}.json",
              {"parsed": {"value": tok, "mfu": mfu,
                          "unit": "tokens/sec/chip"}})
    for r, (tok, p95, ttft) in enumerate(
            [(53.8, 18.1, 10.6), (61.4, 17.6, 10.3), (96.3, 10.0, 7.0)],
            start=1):
        write(f"SERVE_r{r:02d}.json",
              {"value": tok, "speedup_vs_sequential": 2.4 + r,
               "continuous": {"p95_latency_s": p95, "p95_ttft_s": ttft},
               "telemetry": {"compiles": {"serve_decode": 1}},
               "decode_lint": {"shape_churn_findings": 0}, "chaos_ok": 1})
    for r in (1, 2):
        write(f"MULTICHIP_r{r:02d}.json", {"ok": True, "n_devices": 8})
        write(f"LONGCTX_r{r:02d}.json",
              {"results": [{"seq": 8192, "tokens_per_sec": 61000.0 + r,
                            "hbm_peak_bytes": 4.8e10}]})
    return str(tmp_path)


def _series(values, direction="higher", metric="tokens_per_sec",
            name="bench"):
    return {name: [(i + 1, {metric: (v, direction)})
                   for i, v in enumerate(values)]}


def _regressions(findings):
    return [f for f in findings if f["status"] == "REGRESSION"]


# ---------------------------------------------------------------------------
# comparison engine
# ---------------------------------------------------------------------------
def test_flat_history_passes():
    f = bench_sentinel.compare(_series([100.0, 101.0, 99.5, 100.2]))
    assert _regressions(f) == []


def test_twenty_percent_drop_flagged_and_ranked():
    series = _series([100.0, 101.0, 99.5, 80.0])
    series["bench"][-1][1]["mfu"] = (0.5, "higher")  # fine metric rides along
    series["bench"][0][1]["mfu"] = (0.5, "higher")
    series["bench"][1][1]["mfu"] = (0.51, "higher")
    f = bench_sentinel.compare(series)
    regs = _regressions(f)
    assert len(regs) == 1
    assert regs[0]["metric"] == "tokens_per_sec"
    assert regs[0]["delta"] == pytest.approx(-0.2, abs=0.01)
    # ranked: the regression sorts first
    assert f[0]["status"] == "REGRESSION"


def test_improvement_never_flags():
    f = bench_sentinel.compare(_series([100.0, 110.0, 130.0, 160.0]))
    assert _regressions(f) == []


def test_lower_better_direction():
    # latency creeping UP is the regression
    f = bench_sentinel.compare(_series([1.0, 1.02, 0.98, 1.5],
                                       direction="lower",
                                       metric="p95_latency_s"))
    regs = _regressions(f)
    assert len(regs) == 1 and regs[0]["metric"] == "p95_latency_s"
    # latency going DOWN is an improvement
    f = bench_sentinel.compare(_series([1.0, 1.02, 0.98, 0.5],
                                       direction="lower",
                                       metric="p95_latency_s"))
    assert _regressions(f) == []


def test_zero_baseline_lower_better_flags_any_appearance():
    # lint findings / giveups held at 0 historically: ANY appearance flags
    f = bench_sentinel.compare(_series([0.0, 0.0, 0.0, 1.0],
                                       direction="lower",
                                       metric="shape_churn_findings"))
    assert len(_regressions(f)) == 1


def test_contract_metric_flags_any_change():
    # decode must compile exactly once — 1 → 2 is a regression even
    # though 2 is "within 8%+" of nothing
    f = bench_sentinel.compare(_series([1.0, 1.0, 1.0, 2.0],
                                       direction="equal",
                                       metric="decode_compiles"))
    assert len(_regressions(f)) == 1


def test_noise_awareness_widens_tolerance():
    # jittery history (robust cv ≈ 10.4% > the 8% floor): a 9% dip below
    # the median baseline sits inside the widened tolerance → no flag
    jittery = [100.0, 115.0, 87.0, 113.0, 96.9]
    f = bench_sentinel.compare(_series(jittery), window=4, noise_k=1.0)
    assert _regressions(f) == []
    # the SAME 9%-below-baseline dip on a flat history (cv ≈ 0, tolerance
    # floored at 8%) → flagged
    flat = [100.0, 100.5, 99.8, 100.2, 91.1]
    f = bench_sentinel.compare(_series(flat), window=4, noise_k=1.0)
    assert len(_regressions(f)) == 1


def test_step_change_ratchets_baseline():
    # a 60% jump (beyond tolerance → confirmed step-change, not jitter)
    # becomes the new bar: sliding back toward the pre-jump level must
    # flag even though the trailing MEDIAN still sits at the old level
    f = bench_sentinel.compare(_series([100.0, 100.0, 101.0, 160.0, 120.0]))
    regs = _regressions(f)
    assert len(regs) == 1
    assert regs[0]["baseline"] == pytest.approx(160.0)
    # holding the new level is clean
    f = bench_sentinel.compare(_series([100.0, 100.0, 101.0, 160.0, 158.0]))
    assert _regressions(f) == []
    # lower-is-better mirrors: latency halves, then creeps back up
    f = bench_sentinel.compare(_series([10.0, 10.1, 9.9, 5.0, 8.0],
                                       direction="lower",
                                       metric="p95_latency_s"))
    regs = _regressions(f)
    assert len(regs) == 1
    assert regs[0]["baseline"] == pytest.approx(5.0)
    # a within-tolerance wiggle does NOT ratchet (median still rules —
    # see test_noise_awareness_widens_tolerance for the jitter case)
    f = bench_sentinel.compare(_series([100.0, 101.0, 99.5, 100.2]))
    assert f[0]["baseline"] == pytest.approx(100.0)


def test_single_round_series_skipped():
    f = bench_sentinel.compare(_series([42.0]))
    assert f[0]["status"] == "no-history"
    assert _regressions(f) == []


# ---------------------------------------------------------------------------
# a round history on disk (acceptance criterion)
# ---------------------------------------------------------------------------
def test_history_loads_and_passes_clean(history):
    series = bench_sentinel.load_series(history)
    assert "bench" in series and len(series["bench"]) >= 4
    assert "multichip" in series and "serve" in series
    f = bench_sentinel.compare(series)
    assert _regressions(f) == [], bench_sentinel.build_table(f)


def test_history_flags_injected_20pct_drop(history):
    series = bench_sentinel.load_series(history)
    injected = bench_sentinel.inject_round(series, "serve",
                                           "tokens_per_sec", 0.8)
    f = bench_sentinel.compare(injected)
    regs = _regressions(f)
    assert any(r["series"] == "serve" and r["metric"] == "tokens_per_sec"
               for r in regs), bench_sentinel.build_table(f, verbose=True)
    # the untouched metrics still pass
    assert all(r["metric"] == "tokens_per_sec" for r in regs)


def test_multichip_ok_flip_flags(history):
    series = bench_sentinel.load_series(history)
    rounds = series["multichip"]
    last_round, last = rounds[-1]
    flipped = dict(last)
    flipped["dryrun_ok"] = (0.0, "equal")
    series = dict(series)
    series["multichip"] = rounds + [(last_round + 1, flipped)]
    f = bench_sentinel.compare(series)
    assert any(r["metric"] == "dryrun_ok" for r in _regressions(f))


# ---------------------------------------------------------------------------
# CLI surface
# ---------------------------------------------------------------------------
def test_cli_clean_and_smoke(history, capsys):
    assert bench_sentinel.main(["--root", history]) == 0
    assert bench_sentinel.main(["--root", history, "--smoke"]) == 0
    out = capsys.readouterr().out
    assert "SMOKE OK" in out


def test_cli_inject_fails_and_dumps_json(history, tmp_path, capsys):
    out_json = tmp_path / "findings.json"
    rc = bench_sentinel.main([
        "--root", history,
        "--inject", "serve:tokens_per_sec=0.8",
        "--json", str(out_json)])
    assert rc == 1
    table = capsys.readouterr().out
    assert "REGRESSION" in table and "tokens_per_sec" in table
    findings = json.loads(out_json.read_text())
    assert any(f["status"] == "REGRESSION" for f in findings)


def test_cli_no_history_exit_2(tmp_path):
    assert bench_sentinel.main(["--root", str(tmp_path)]) == 2


def test_cli_bad_inject_spec(history):
    with pytest.raises(ValueError, match="bad --inject"):
        bench_sentinel.main(["--root", history, "--inject", "nonsense"])
