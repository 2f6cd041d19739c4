"""bench.py driver contract: config order, headline priority, crash
resilience, measured-cost-history estimates, and the no-chip failure.

The driver invokes ``python bench.py`` blind and parses the LAST complete
JSON line; these tests pin that contract with the heavy configs mocked and
the device faked (the suite runs on CPU, where the real bench refuses to
measure).
"""
import importlib
import inspect
import json
import sys
import types

import pytest

_FAKE_CHIP = {"platform": "tpu", "device_kind": "TPU v5 lite", "count": 1}


def _load_bench(tmp_path, monkeypatch, scale_behavior, xgb_behavior=None):
    """Import a fresh bench module wired to mock workloads.

    ``scale_behavior(rows, cols, which_grid)`` returns a result dict or
    raises; titanic + kernels are stubbed cheap.
    """
    import bench as bench_mod

    bench = importlib.reload(bench_mod)
    monkeypatch.setattr(bench, "COST_HISTORY",
                        str(tmp_path / "cost_history.json"))

    def fake_titanic():
        return {"metric": "titanic_automl_train_wall_clock", "value": 1.0,
                "unit": "s", "cold_s": 1.0, "warm_s": 1.0,
                "vs_baseline": 2.0, "aupr": 0.8, "auroc": 0.85,
                "reference_aupr_range": [0.675, 0.810],
                "baseline_s": 180.0, "baseline_kind": "spark_estimate"}

    monkeypatch.setattr(bench, "run_titanic", fake_titanic)

    calls = []

    fake_scale = types.ModuleType("bench_scale")

    def scale_run(rows, cols, folds=3, which_grid="light", warmup=False,
                  baseline_s=1800.0):
        calls.append((rows, cols, which_grid))
        out = scale_behavior(rows, cols, which_grid)
        if isinstance(out, Exception):
            raise out
        return out

    fake_scale.run = scale_run
    monkeypatch.setitem(sys.modules, "bench_scale", fake_scale)

    fake_xgb = types.ModuleType("bench_xgb_wide")

    def xgb_run():
        calls.append(("xgb",))
        if xgb_behavior is not None:
            out = xgb_behavior()
            if isinstance(out, Exception):
                raise out
            return out
        return {"metric": "xgb_wide_sparse_fit_wall_clock", "value": 5.0,
                "unit": "s"}

    fake_xgb.run = xgb_run
    monkeypatch.setitem(sys.modules, "bench_xgb_wide", fake_xgb)

    fake_kern = types.ModuleType("bench_kernels")
    fake_kern.run = lambda: (calls.append(("kernels",))
                             or {"hist_mfu": 0.01})
    monkeypatch.setitem(sys.modules, "bench_kernels", fake_kern)

    monkeypatch.setattr(bench, "_device", lambda: dict(_FAKE_CHIP))
    return bench, calls


def _grid_result(rows, cols, which_grid, value=10.0):
    return {"candidates": 6, "candidate_errors": 0, "grid": which_grid,
            "metric": "scale_automl_train_wall_clock", "rows": rows,
            "cols": cols, "value": value, "unit": "s", "vs_baseline": 2.0,
            "aupr": 0.9, "auroc": 0.95, "datagen_s": 1.0,
            "baseline_s_assumed": 1800.0, "warmup_s": 0.0, "phases": {},
            "transfers": {}}


def _run_main(bench, capsys):
    bench.main()
    lines = [ln for ln in capsys.readouterr().out.splitlines() if ln.strip()]
    return json.loads(lines[-1])


class TestBenchContract:
    def test_order_and_headline_when_all_pass(self, tmp_path, monkeypatch,
                                              capsys):
        bench, calls = _load_bench(
            tmp_path, monkeypatch,
            lambda r, c, g: _grid_result(r, c, g))
        monkeypatch.setenv("TMOG_BENCH_BUDGET_S", "100000")
        monkeypatch.delenv("TMOG_BENCH_SKIP_1M_DEFAULT", raising=False)
        last = _run_main(bench, capsys)
        grid_calls = [c for c in calls if len(c) == 3]
        # light 1M, 100k default, then the 1M default LAST — in process,
        # through the same bench_scale.run as every other grid config
        assert grid_calls == [(1_000_000, 500, "light"),
                              (100_000, 500, "default"),
                              (1_000_000, 500, "default")]
        assert calls.index(("xgb",)) < calls.index(
            (1_000_000, 500, "default"))
        # a COMPLETED 1M default grid is the headline
        assert last["metric"] == "automl_default_grid_1m_x_500_wall_clock"
        assert set(last["configs"]) >= {"titanic", "scale_1m_x_500",
                                        "default_grid_1m_x_500",
                                        "xgb_wide", "kernels"}
        # every line names the device it ran on
        assert last["device"] == _FAKE_CHIP
        assert last["backend"] == "tpu"
        assert "backend_fallback" not in last

    def test_headline_priority_when_default_1m_crashes(self, tmp_path,
                                                       monkeypatch, capsys):
        def behavior(rows, cols, grid):
            if rows == 1_000_000 and grid == "default":
                return RuntimeError("TPU worker crashed")
            return _grid_result(rows, cols, grid)

        bench, _ = _load_bench(tmp_path, monkeypatch, behavior)
        monkeypatch.setenv("TMOG_BENCH_BUDGET_S", "100000")
        monkeypatch.delenv("TMOG_BENCH_SKIP_1M_DEFAULT", raising=False)
        last = _run_main(bench, capsys)
        # the 1M LIGHT grid headlines (not the 100k diagnostic), and the
        # crash is recorded — never silently skipped
        assert last["metric"] == "automl_1m_x_500_light_grid_wall_clock"
        assert "error" in last["configs"]["default_grid_1m_x_500"]
        assert "xgb_wide" in last["configs"]

    def test_100k_headlines_only_without_any_1m_result(self, tmp_path,
                                                       monkeypatch, capsys):
        def behavior(rows, cols, grid):
            if rows == 1_000_000:
                return RuntimeError("boom")
            return _grid_result(rows, cols, grid)

        bench, _ = _load_bench(tmp_path, monkeypatch, behavior)
        monkeypatch.setenv("TMOG_BENCH_BUDGET_S", "100000")
        monkeypatch.delenv("TMOG_BENCH_SKIP_1M_DEFAULT", raising=False)
        last = _run_main(bench, capsys)
        assert last["metric"] == "automl_default_grid_100k_x_500_wall_clock"

    def test_cost_history_sig_mismatch_falls_back(self, tmp_path,
                                                  monkeypatch):
        bench, _ = _load_bench(tmp_path, monkeypatch,
                               lambda r, c, g: _grid_result(r, c, g))
        bench._record_cost("cfg", 123.0, cold=False, sig="old-shape")
        est, src = bench._estimate("cfg", 50.0, sig="new-shape")
        assert (est, src) == (50.0, "assumed")
        est, src = bench._estimate("cfg", 50.0, sig="old-shape")
        assert (est, src) == (123.0, "measured_history")

    def test_diagnostic_skip_knob_records_reason(self, tmp_path,
                                                 monkeypatch, capsys):
        bench, calls = _load_bench(
            tmp_path, monkeypatch, lambda r, c, g: _grid_result(r, c, g))
        monkeypatch.setenv("TMOG_BENCH_BUDGET_S", "100000")
        monkeypatch.setenv("TMOG_BENCH_SKIP_1M_DEFAULT", "1")
        last = _run_main(bench, capsys)
        assert (1_000_000, 500, "default") not in calls
        assert "skipped" in last["configs"]["default_grid_1m_x_500"]
        assert "diagnostic" in str(
            last["configs"]["default_grid_1m_x_500"]["skipped"])


class TestNoChip:
    """A run that finds no accelerator ends in a non-zero exit and ONE
    parseable JSON error line — never a CPU re-exec, never a child
    process that would need the chip its parent holds."""

    def _bench(self, tmp_path, monkeypatch):
        import bench as bench_mod

        bench = importlib.reload(bench_mod)
        monkeypatch.setattr(bench, "COST_HISTORY",
                            str(tmp_path / "ch.json"))
        monkeypatch.setattr(
            bench, "run_titanic",
            lambda: pytest.fail("a config ran without a chip"))
        return bench

    def _fails_with_json(self, bench, capsys):
        with pytest.raises(SystemExit) as exc:
            bench.main()
        assert exc.value.code not in (0, None)
        lines = [ln for ln in capsys.readouterr().out.splitlines()
                 if ln.strip()]
        assert len(lines) == 1
        doc = json.loads(lines[0])
        assert doc["ok"] is False
        return doc

    def test_cpu_platform_is_a_failure(self, tmp_path, monkeypatch, capsys):
        # the suite runs under JAX_PLATFORMS=cpu: the REAL device probe
        bench = self._bench(tmp_path, monkeypatch)
        assert bench._device()["platform"] == "cpu"
        doc = self._fails_with_json(bench, capsys)
        assert "no accelerator" in doc["error"] and "cpu" in doc["error"]

    def test_backend_init_failure_is_a_failure(self, tmp_path, monkeypatch,
                                               capsys):
        bench = self._bench(tmp_path, monkeypatch)

        def boom():
            raise RuntimeError("Unable to initialize backend 'tpu'")

        monkeypatch.setattr(bench, "_device", boom)
        doc = self._fails_with_json(bench, capsys)
        assert "Unable to initialize backend" in doc["error"]

    def test_no_child_process_and_no_failover(self):
        import bench

        src = inspect.getsource(bench)
        assert "subprocess" not in src
        assert "execv" not in src
        for gone in ("_ensure_backend", "_backend_failover", "_guarded",
                     "_is_backend_unavailable", "_run_headline_subprocess",
                     "backend_fallback"):
            assert gone not in src, gone
