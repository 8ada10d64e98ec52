"""The repro.sweep harness: planning, resume, dry-run, analysis.

The resumability/byte-identity contract (ISSUE 6 acceptance): an
interrupted-and-resumed sweep, a process-parallel sweep and a serial
uninterrupted sweep must all finalize to byte-identical
``points.jsonl``; ``--dry-run`` must reject sub-Vt supplies and
cutoff-infeasible CIM points with recorded reasons; logs and reports
reject version/config-hash mismatches loudly.

The fast tests run on the pure ``grid-echo`` measure (no jax); the
calibration-backed ``pareto`` measure is covered by one smoke test
plus ``benchmarks/pareto.py --smoke`` in scripts/check.sh.
"""

import json
import pathlib

import pytest

from repro.sweep import analysis, measures, plan, report, runner
from repro.sweep.config import SWEEP_VERSION, SweepConfig, load_config


def echo_config(tmp_path, **over) -> SweepConfig:
    d = {
        "name": "echo",
        "measure": "grid-echo",
        "axes": {"adc_bits": [3, 4], "vdd": [0.6, 0.9]},
        "analysis": "table",
        "out_dir": str(tmp_path / "out"),
    }
    d.update(over)
    return SweepConfig.from_dict(d)


class TestConfigAndPlan:
    def test_hash_excludes_out_dir(self, tmp_path):
        a = echo_config(tmp_path / "a")
        b = echo_config(tmp_path / "b")
        assert a.config_hash == b.config_hash
        assert a.sweep_dir != b.sweep_dir

    def test_hash_changes_with_axes_and_params(self, tmp_path):
        a = echo_config(tmp_path)
        b = echo_config(tmp_path, axes={"adc_bits": [3], "vdd": [0.6]})
        c = a.override(params={"k": 1})
        assert len({a.config_hash, b.config_hash, c.config_hash}) == 3

    def test_expand_is_ordered_and_stable(self, tmp_path):
        cfg = echo_config(tmp_path)
        pts = plan.expand(cfg)
        assert [p.index for p in pts] == [0, 1, 2, 3]
        # sorted axis names, values in config order
        assert [p.values for p in pts] == [
            {"adc_bits": 3, "vdd": 0.6},
            {"adc_bits": 3, "vdd": 0.9},
            {"adc_bits": 4, "vdd": 0.6},
            {"adc_bits": 4, "vdd": 0.9},
        ]
        assert [p.point_id for p in pts] == [
            p.point_id for p in plan.expand(echo_config(tmp_path / "x"))
        ]
        assert len({p.point_id for p in pts}) == 4

    def test_bad_configs_raise(self):
        with pytest.raises(ValueError, match="non-empty 'name'"):
            SweepConfig.from_dict({"name": "", "measure": "m",
                                   "axes": {"a": [1]}})
        with pytest.raises(ValueError, match="axes"):
            SweepConfig.from_dict({"name": "x", "measure": "m",
                                   "axes": {}})
        with pytest.raises(ValueError, match="axis 'a'"):
            SweepConfig.from_dict({"name": "x", "measure": "m",
                                   "axes": {"a": []}})
        with pytest.raises(ValueError, match="unknown sweep config field"):
            SweepConfig.from_dict({"name": "x", "measure": "m",
                                   "axes": {"a": [1]}, "bogus": 1})

    def test_load_config_json_and_py(self, tmp_path):
        j = tmp_path / "c.json"
        j.write_text(json.dumps({"name": "j", "measure": "grid-echo",
                                 "axes": {"a": [1, 2]}}))
        assert load_config(j).name == "j"
        p = tmp_path / "c.py"
        p.write_text(
            "CONFIG = {'name': 'p', 'measure': 'grid-echo',\n"
            "          'axes': {'a': list(range(3))}}\n"
        )
        cfg = load_config(p)
        assert cfg.axes["a"] == (0, 1, 2)
        with pytest.raises(FileNotFoundError):
            load_config(tmp_path / "missing.json")

    def test_unknown_measure_rejected(self, tmp_path):
        cfg = echo_config(tmp_path, measure="no-such-measure")
        with pytest.raises(ValueError, match="unknown measure"):
            runner.dry_run(cfg)

    def test_module_attr_measure_resolves(self):
        m = measures.resolve("repro.sweep.measures:_grid_echo")
        assert m.fn is measures._grid_echo


class TestDryRun:
    def test_rejects_sub_vt_vdd_and_infeasible_cutoff(self, tmp_path):
        cfg = echo_config(
            tmp_path,
            axes={"rows_active": [16], "adc_bits": [4],
                  "cutoff": [0.5, 0.9], "vdd": [0.3, 0.6]},
        )
        recs = runner.dry_run(cfg)
        by_point = {
            (r["point"]["cutoff"], r["point"]["vdd"]): r for r in recs
        }
        assert by_point[(0.5, 0.6)]["feasible"]
        sub_vt = by_point[(0.5, 0.3)]
        assert not sub_vt["feasible"] and "Vt" in sub_vt["reason"]
        bad_cut = by_point[(0.9, 0.6)]
        assert not bad_cut["feasible"]
        assert "pMAC spacing" in bad_cut["reason"]

    def test_rejects_unknown_variant(self, tmp_path):
        cfg = echo_config(tmp_path, axes={"variant": ["p8t", "bogus"]})
        recs = runner.dry_run(cfg)
        assert recs[0]["feasible"]
        assert not recs[1]["feasible"]
        assert "unknown variant" in recs[1]["reason"]

    def test_shape_axis_names_vs_tuning_cells(self, tmp_path):
        """A string "shape" is a launch-cell name (registry-checked);
        a [m, k, n] list is a kernel tuning cell and passes through to
        the measure's own validation."""
        named = echo_config(
            tmp_path, axes={"arch": ["whisper_tiny"],
                            "shape": ["decode_32k", "bogus_shape"]}
        )
        recs = runner.dry_run(named)
        assert recs[0]["feasible"]  # values keep config order
        assert not recs[1]["feasible"]
        assert "unknown shape" in recs[1]["reason"]
        cells = echo_config(
            tmp_path, name="cells",
            axes={"variant": ["p8t"], "shape": [[8, 512, 512]]},
        )
        assert all(r["feasible"] for r in runner.dry_run(cells))

    def test_dry_run_executes_nothing(self, tmp_path):
        cfg = echo_config(tmp_path)
        runner.dry_run(cfg)
        assert not cfg.points_path.exists()


class TestRunnerResume:
    def test_infeasible_points_recorded_as_skips(self, tmp_path):
        cfg = echo_config(
            tmp_path, axes={"adc_bits": [4], "vdd": [0.3, 0.6]},
        )
        rep = runner.run(cfg, log=lambda _s: None)
        assert (rep.n_ok, rep.n_skipped) == (1, 1)
        recs = sorted(runner.read_points(cfg).values(),
                      key=lambda r: r["index"])
        assert recs[0]["status"] == "skipped"
        assert "Vt" in recs[0]["reason"]
        assert recs[1]["status"] == "ok"

    def test_interrupted_resume_is_byte_identical(self, tmp_path):
        straight = echo_config(tmp_path / "a")
        rep = runner.run(straight, log=lambda _s: None)
        assert rep.finalized and rep.n_ok == 4

        # "Kill" after 2 points, then restart: the resumed run must
        # skip the completed points and finalize identical bytes.
        resumed = echo_config(tmp_path / "b")
        rep1 = runner.run(resumed, max_points=2, log=lambda _s: None)
        assert not rep1.finalized and rep1.n_ok == 2
        rep2 = runner.run(resumed, log=lambda _s: None)
        assert rep2.finalized
        assert rep2.n_prior == 2 and rep2.n_ok == 2
        assert (resumed.points_path.read_bytes()
                == straight.points_path.read_bytes())

    def test_torn_trailing_line_is_dropped_and_rerun(self, tmp_path):
        cfg = echo_config(tmp_path)
        runner.run(cfg, max_points=2, log=lambda _s: None)
        with cfg.points_path.open("a") as f:
            f.write('{"version": 1, "config_hash": "trunc')  # torn
        rep = runner.run(cfg, log=lambda _s: None)
        assert rep.finalized and rep.n_prior == 2
        clean = echo_config(tmp_path / "clean")
        runner.run(clean, log=lambda _s: None)
        assert (cfg.points_path.read_bytes()
                == clean.points_path.read_bytes())

    def test_corrupt_mid_log_raises(self, tmp_path):
        cfg = echo_config(tmp_path)
        runner.run(cfg, max_points=2, log=lambda _s: None)
        lines = cfg.points_path.read_text().splitlines()
        lines[0] = "not json"
        cfg.points_path.write_text("\n".join(lines) + "\n")
        with pytest.raises(ValueError, match="corrupt record"):
            runner.read_points(cfg)

    def test_mismatched_config_hash_rejected(self, tmp_path):
        cfg = echo_config(tmp_path)
        runner.run(cfg, log=lambda _s: None)
        changed = echo_config(tmp_path, params={"new": 1})
        with pytest.raises(ValueError, match="config_hash"):
            runner.run(changed, log=lambda _s: None)

    def test_mismatched_version_rejected(self, tmp_path):
        cfg = echo_config(tmp_path)
        runner.run(cfg, log=lambda _s: None)
        recs = [json.loads(line) for line in
                cfg.points_path.read_text().splitlines()]
        recs[0]["version"] = SWEEP_VERSION + 1
        cfg.points_path.write_text(
            "".join(runner.record_line(r) + "\n" for r in recs)
        )
        with pytest.raises(ValueError, match="version"):
            runner.read_points(cfg)

    def test_parallel_run_matches_serial_bytes(self, tmp_path):
        serial = echo_config(tmp_path / "s")
        runner.run(serial, log=lambda _s: None)
        par = echo_config(tmp_path / "p")
        rep = runner.run(par, jobs=2, log=lambda _s: None)
        assert rep.finalized
        assert (par.points_path.read_bytes()
                == serial.points_path.read_bytes())

    def test_parallel_run_refused_on_tpu(self, tmp_path, monkeypatch):
        """Worker processes would each claim the one chip: --jobs > 1 is
        refused on a TPU backend before any point runs."""
        import jax

        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        cfg = echo_config(tmp_path)
        with pytest.raises(ValueError, match="one process"):
            runner.run(cfg, jobs=2, log=lambda _s: None)
        assert not cfg.points_path.exists()


class TestAnalysis:
    def test_table_renderer_is_deterministic(self, tmp_path):
        cfg = echo_config(tmp_path)
        runner.run(cfg, log=lambda _s: None)
        first = [p.read_bytes() for p in analysis.analyze(cfg)]
        second = [p.read_bytes() for p in analysis.analyze(cfg)]
        assert first == second
        summary = json.loads(first[0])
        assert summary["config_hash"] == cfg.config_hash
        assert summary["n_points"] == 4

    def test_analyze_without_run_raises(self, tmp_path):
        cfg = echo_config(tmp_path)
        with pytest.raises(ValueError, match="no points recorded"):
            analysis.analyze(cfg)

    def test_unknown_renderer_raises(self, tmp_path):
        cfg = echo_config(tmp_path, analysis="bogus")
        runner.run(cfg, log=lambda _s: None)
        with pytest.raises(ValueError, match="unknown analysis"):
            analysis.analyze(cfg)

    def test_load_report_rejects_version_mismatch(self, tmp_path):
        payload = report.pareto_payload(
            "m", [], cost_unit="fJ/MAC", slack=2.0, grid=None,
        )
        jpath, _ = report.write_payload(payload, tmp_path)
        assert report.load_report(jpath)["model"] == "m"
        stale = dict(payload, version=1)
        jpath.write_text(json.dumps(stale))
        with pytest.raises(ValueError, match="report version"):
            report.load_report(jpath)

    def test_autotune_renderer_roundtrips_cache(self, tmp_path):
        from repro.kernels import autotune

        cfg = echo_config(
            tmp_path, name="tune", measure="grid-echo",
            analysis="autotune", params={"arch": "testarch"},
            axes={"variant": ["p8t"], "shape": [[8, 512, 512]]},
        )
        # Hand-write ok records in the autotune result shape (the real
        # measure times kernels; rendering is what's under test).
        pts = plan.expand(cfg)
        recs = [
            runner._make_record(
                cfg, p, status="ok",
                result={
                    "variant": p.values["variant"],
                    "shape": list(p.values["shape"]),
                    "cell": [8, 512, 512],
                    "backend": "ref", "block": None, "us": 12.5,
                },
            )
            for p in pts
        ]
        cfg.sweep_dir.mkdir(parents=True)
        cfg.points_path.write_text(
            "".join(runner.record_line(r) + "\n" for r in recs)
        )
        (path,) = analysis.analyze(cfg)
        payload = json.loads(path.read_text())
        assert payload["config_hash"] == cfg.config_hash
        cache = autotune.TuningCache.from_json(payload)
        w = cache.lookup("p8t", (8, 512, 512))
        assert w is not None and w.backend == "ref"


class TestParetoMeasureSmoke:
    def test_ci_smoke_config_end_to_end(self, tmp_path):
        cfg = load_config(
            pathlib.Path(__file__).resolve().parents[1]
            / "configs" / "sweeps" / "ci_smoke.json"
        ).override(out_dir=str(tmp_path))
        recs = runner.dry_run(cfg)
        assert all(r["feasible"] for r in recs)
        rep = runner.run(cfg, log=lambda _s: None)
        assert rep.finalized and rep.n_ok == 2
        jpath, mpath = analysis.analyze(cfg)
        payload = report.load_report(jpath)
        assert payload["cost_unit"] == "fJ/MAC"
        assert len(payload["points"]) == 2
        assert any(p["frontier"] for p in payload["points"])
        assert payload["config_hash"] == cfg.config_hash
