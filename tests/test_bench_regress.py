"""Bench regression sentinel (tools/bench_regress.py).

The sentinel walks the BENCH_MEASURED_*.json trajectory and compares each
headline key's newest occurrence against its most recent prior occurrence
(or a parsed BENCH_r0*.json baseline). These tests synthesize small
trajectories in tmp dirs and also assert the REAL repo trajectory is green —
the acceptance criterion is "flags a synthetically degraded artifact while
passing on the repo's actual history".
"""

import json
import os
import sys

import pytest

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "tools"))

import bench_regress  # noqa: E402



def _write(tmp_path, name, doc):
    (tmp_path / name).write_text(json.dumps(doc))


class TestFlatten:
    def test_numeric_leaves_dotted_and_bools_excluded(self):
        flat = bench_regress.flatten(
            {"a": {"b": 1, "ok": True}, "c": 2.5, "s": "text"})
        assert flat == {"a.b": 1.0, "c": 2.5}

    def test_ladder_value_is_metric_qualified(self):
        flat = bench_regress.flatten(
            {"metric": "llm_train_tokens_per_sec", "value": 100.0,
             "short_window": {"metric": "fedavg_rounds_per_hr", "value": 7.0}})
        assert flat["value:llm_train_tokens_per_sec"] == 100.0
        assert flat["short_window.value:fedavg_rounds_per_hr"] == 7.0
        assert "value" not in flat


class TestCompare:
    def test_degraded_artifact_is_flagged(self, tmp_path):
        _write(tmp_path, "BENCH_MEASURED_20260101T000000Z.json",
               {"fedavg_rounds_per_hr": 100.0, "mfu": 0.30})
        _write(tmp_path, "BENCH_MEASURED_20260102T000000Z.json",
               {"fedavg_rounds_per_hr": 50.0, "mfu": 0.31})
        report = bench_regress.compare(str(tmp_path), 0.10)
        regressed = {r["key"] for r in report["regressions"]}
        assert regressed == {"fedavg_rounds_per_hr"}
        row = report["regressions"][0]
        assert row["new"] == 50.0 and row["old"] == 100.0
        assert row["delta_pct"] == -50.0
        assert bench_regress.main(["--repo", str(tmp_path)]) == 1

    def test_lower_is_better_direction(self, tmp_path):
        _write(tmp_path, "BENCH_MEASURED_20260101T000000Z.json",
               {"ckpt_enqueue_ms": 0.5})
        _write(tmp_path, "BENCH_MEASURED_20260102T000000Z.json",
               {"ckpt_enqueue_ms": 1.5})
        report = bench_regress.compare(str(tmp_path), 0.10)
        assert [r["key"] for r in report["regressions"]] == \
            ["ckpt_enqueue_ms"]

    def test_improvement_and_within_threshold_pass(self, tmp_path):
        _write(tmp_path, "BENCH_MEASURED_20260101T000000Z.json",
               {"fedavg_rounds_per_hr": 100.0, "agg_wall_s": 10.0})
        _write(tmp_path, "BENCH_MEASURED_20260102T000000Z.json",
               {"fedavg_rounds_per_hr": 95.0, "agg_wall_s": 8.0})
        report = bench_regress.compare(str(tmp_path), 0.10)
        assert report["compared"] == 2
        assert report["regressions"] == []
        assert bench_regress.main(["--repo", str(tmp_path)]) == 0

    def test_stage_isolated_runs_compare_per_key(self, tmp_path):
        # the key regressed two runs back; the newest artifact measured a
        # DIFFERENT stage and must not mask it
        _write(tmp_path, "BENCH_MEASURED_20260101T000000Z.json",
               {"decode_tokens_per_sec": 200.0})
        _write(tmp_path, "BENCH_MEASURED_20260102T000000Z.json",
               {"decode_tokens_per_sec": 90.0})
        _write(tmp_path, "BENCH_MEASURED_20260103T000000Z.json",
               {"resnet56_steps_per_sec": 5.0})
        report = bench_regress.compare(str(tmp_path), 0.10)
        assert [r["key"] for r in report["regressions"]] == \
            ["decode_tokens_per_sec"]

    def test_different_ladder_metrics_never_cross_compare(self, tmp_path):
        _write(tmp_path, "BENCH_MEASURED_20260101T000000Z.json",
               {"metric": "llm_train_tokens_per_sec", "value": 40000.0})
        _write(tmp_path, "BENCH_MEASURED_20260102T000000Z.json",
               {"metric": "fedavg_rounds_per_hr", "value": 8.0})
        report = bench_regress.compare(str(tmp_path), 0.10)
        assert report["compared"] == 0

    def test_baseline_fallback_for_single_occurrence(self, tmp_path):
        _write(tmp_path, "BENCH_r01.json",
               {"parsed": {"metric": "fedavg_rounds_per_hr", "value": 100.0}})
        _write(tmp_path, "BENCH_r02.json", {"parsed": None})
        _write(tmp_path, "BENCH_MEASURED_20260102T000000Z.json",
               {"metric": "fedavg_rounds_per_hr", "value": 40.0})
        report = bench_regress.compare(str(tmp_path), 0.10)
        assert len(report["regressions"]) == 1
        assert report["regressions"][0]["ref"] == "BENCH_r01.json"

    def test_nonheadline_keys_ignored(self, tmp_path):
        _write(tmp_path, "BENCH_MEASURED_20260101T000000Z.json",
               {"elapsed_s": 100.0, "n_devices": 8})
        _write(tmp_path, "BENCH_MEASURED_20260102T000000Z.json",
               {"elapsed_s": 900.0, "n_devices": 1})
        assert bench_regress.compare(str(tmp_path), 0.10)["compared"] == 0

    def test_empty_dir_is_clean_exit(self, tmp_path):
        report = bench_regress.compare(str(tmp_path), 0.10)
        assert report["newest"] is None
        assert bench_regress.main(["--repo", str(tmp_path)]) == 0


class TestRenderTable:
    def test_table_marks_regressions(self, tmp_path):
        _write(tmp_path, "BENCH_MEASURED_20260101T000000Z.json",
               {"mfu": 0.30})
        _write(tmp_path, "BENCH_MEASURED_20260102T000000Z.json",
               {"mfu": 0.10})
        report = bench_regress.compare(str(tmp_path), 0.10)
        table = bench_regress.render_table(report)
        assert "REGRESS" in table
        assert "1 regression(s) over threshold" in table
