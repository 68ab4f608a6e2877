"""Performance observatory: machine profiler, kernel catalog, regression gate.

Everything runs at toy sizes — tiny probe overrides for the machine file,
the smallest rungs for the catalog — so the suite exercises the real
lower/compile/cost/measure path without benchmark-scale wall time.  The
numbers themselves are not asserted (this is a shared CI box); the
*structure* is: positive FLOPs and wall times, all four kernels present,
the regression gate's exit-code contract, and the v5e preset pinned to the
constants ``benchmarks/roofline.py`` documents as its fallback.
"""

import json
import os
import subprocess
import sys

import pytest

from repro.perf import PRESETS, load_machine, profile_machine, save_machine
from repro.perf import catalog as catalog_lib
from repro.perf import regress
from repro.perf import report as report_lib

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# --- machine profiler --------------------------------------------------------


@pytest.fixture(scope="module")
def tiny_machine():
    # toy probe sizes: the path is real, the wall time is milliseconds
    return profile_machine(fast=True, matmul_n=64, stream_n=1 << 12, reps=1)


def test_profile_machine_shape(tiny_machine):
    m = tiny_machine
    assert m["source"] == "measured"
    assert m["peak_flops"] > 0 and m["mem_bw"] > 0 and m["reduce_bw"] > 0
    assert m["ici_bw"] is None  # single-device pytest process
    assert m["meta"]["platform"] == "cpu"
    assert set(m["probes"]) == {
        "matmul_f64",
        "matmul_f32",
        "saxpy",
        "reduction",
        "ici_ppermute",
    }


def test_machine_save_load_round_trip(tiny_machine, tmp_path):
    path = str(tmp_path / "machine.json")
    save_machine(tiny_machine, path)
    loaded = load_machine(path)
    assert loaded == json.loads(json.dumps(tiny_machine))  # float-exact via json


def test_load_machine_rejects_non_machine_file(tmp_path):
    path = str(tmp_path / "bogus.json")
    with open(path, "w") as f:
        json.dump({"metrics": {}}, f)
    with pytest.raises(ValueError, match="not a machine file"):
        load_machine(path)


def test_resolve_machine_explicit_path_must_exist(tmp_path):
    from repro.perf import resolve_machine

    with pytest.raises(FileNotFoundError):
        resolve_machine(str(tmp_path / "nope.json"))


def test_resolve_machine_refuses_file_from_another_device(tmp_path, monkeypatch):
    from repro.perf import machine as machine_lib

    path = str(tmp_path / "machine.json")
    save_machine({"peak_flops": 1.0, "mem_bw": 1.0, "meta": {"device_kind": "cpu"}}, path)
    assert machine_lib.resolve_machine(path)["peak_flops"] == 1.0  # tests run on cpu
    monkeypatch.setattr(machine_lib, "_device_kind", lambda: "TPU v5 lite")
    with pytest.raises(ValueError, match="measured on 'cpu'"):
        machine_lib.resolve_machine(path)
    # the committed default file (a CPU run) is skipped for the preset
    assert machine_lib.resolve_machine() == PRESETS["TPU v5 lite"]


def test_resolve_machine_refuses_unknown_device(monkeypatch):
    from repro.perf import machine as machine_lib

    monkeypatch.setattr(machine_lib, "_device_kind", lambda: "TPU v99")
    with pytest.raises(ValueError, match="no preset for device kind 'TPU v99'"):
        machine_lib.resolve_machine()


def test_v5e_preset_pinned_to_roofline_constants():
    """The documented fallback can never drift from the retired constants."""
    sys.path.insert(0, _REPO)
    try:
        from benchmarks import roofline
    finally:
        sys.path.pop(0)
    v5e = PRESETS["TPU v5 lite"]
    assert v5e["peak_flops"] == roofline.PEAK_FLOPS
    assert v5e["mem_bw"] == roofline.HBM_BW
    assert v5e["ici_bw"] == roofline.ICI_BW


# --- kernel cost catalog -----------------------------------------------------


@pytest.fixture(scope="module")
def tiny_catalog(tiny_machine):
    from repro.core.config import QuadratureConfig

    # far smaller than default_configs(fast=True): the suite exercises the
    # lower/cost/measure path, not benchmark-scale shapes
    cub = QuadratureConfig(d=3, integrand="f4", capacity=1 << 8).validate()
    veg = QuadratureConfig(
        d=4, integrand="f4", backend="vegas", mc_samples=2048, mc_shards=8
    ).validate()
    svc = QuadratureConfig(
        d=2,
        integrand="genz_gaussian",
        capacity=1 << 8,
        batch_slots=4,
        sync_every=4,
    ).validate()
    cfgs = {
        "gm_eval": cub,
        "advance": cub,
        "vegas_iterate": veg,
        "service_dispatch": svc,
    }
    return catalog_lib.build_catalog(tiny_machine, fast=True, reps=1, configs=cfgs)


def test_catalog_covers_required_kernels(tiny_catalog):
    kernels = {e["kernel"] for e in tiny_catalog["entries"]}
    # the acceptance set: GM eval, VEGAS iterate, fused service dispatch
    assert {"gm_eval", "vegas_iterate", "service_dispatch"} <= kernels
    assert kernels <= set(catalog_lib.KERNELS)


def test_catalog_entries_are_roofline_complete(tiny_catalog):
    for e in tiny_catalog["entries"]:
        assert e["flops"] > 0, e["kernel"]
        assert e["bytes"] > 0, e["kernel"]
        assert e["measured_s"] > 0, e["kernel"]
        assert e["predicted_s"] > 0, e["kernel"]
        assert e["roofline_frac"] == pytest.approx(
            e["predicted_s"] / e["measured_s"]
        )
        assert e["dominant"] in ("compute", "memory")
        assert e["scan_trips"] >= 1


def test_catalog_scales_dispatch_by_scan_trips(tiny_catalog):
    disp = [e for e in tiny_catalog["entries"] if e["kernel"] == "service_dispatch"]
    assert disp, "fused dispatch missing from catalog"
    for e in disp:
        # HloCostAnalysis counts the scan body once; the catalog multiplies
        # by the known trip count (sync_every)
        assert e["scan_trips"] > 1
        assert e["flops_total"] == pytest.approx(e["flops"] * e["scan_trips"])
        assert e["bytes_total"] == pytest.approx(e["bytes"] * e["scan_trips"])


def test_catalog_round_trip_and_table(tiny_catalog, tmp_path):
    path = str(tmp_path / "catalog.json")
    catalog_lib.save_catalog(tiny_catalog, path)
    loaded = catalog_lib.load_catalog(path)
    assert loaded["entries"] == json.loads(json.dumps(tiny_catalog["entries"]))
    table = catalog_lib.render_table(loaded["entries"])
    assert "roofline frac" in table
    for k in ("gm_eval", "vegas_iterate", "service_dispatch"):
        assert k in table


# --- regression gate ---------------------------------------------------------


def _summary(metrics, **meta):
    base_meta = {
        "date": "2026-08-08T00:00:00",
        "git_sha": "deadbee",
        "jax_version": "0.4.37",
        "platform": "cpu",
        "device_kind": "cpu",
        "device_count": 1,
    }
    base_meta.update(meta)
    return {"meta": base_meta, "metrics": metrics}


def _write(tmp_path, name, payload):
    path = str(tmp_path / name)
    with open(path, "w") as f:
        json.dump(payload, f)
    return path


def test_regress_identical_exits_zero(tmp_path):
    base = _write(tmp_path, "base.json", _summary({"a": 100.0, "b": 5.0}))
    assert regress.main([base, base]) == 0


def test_regress_fails_on_1p5x_slowdown(tmp_path):
    base = _write(tmp_path, "base.json", _summary({"a": 100.0, "b": 5.0}))
    cand = _write(tmp_path, "cand.json", _summary({"a": 150.0, "b": 5.0}))
    assert regress.main([base, cand]) == 1


def test_regress_warn_zone_exits_zero(tmp_path):
    # 1.2x: above warn (1.1) but below fail (1.3) — warns, still passes
    base = _write(tmp_path, "base.json", _summary({"a": 100.0}))
    cand = _write(tmp_path, "cand.json", _summary({"a": 120.0}))
    assert regress.main([base, cand]) == 0
    rows, _ = regress.compare(_summary({"a": 100.0}), _summary({"a": 120.0}))
    assert rows[0]["verdict"] == "warn"


def test_regress_relaxed_thresholds(tmp_path):
    # the CI cross-machine mode: 1.5x passes under --fail-ratio 10
    base = _write(tmp_path, "base.json", _summary({"a": 100.0}))
    cand = _write(tmp_path, "cand.json", _summary({"a": 150.0}))
    assert regress.main([base, cand, "--fail-ratio", "10", "--warn-ratio", "3"]) == 0


def test_regress_platform_mismatch_rejected(tmp_path):
    base = _write(tmp_path, "base.json", _summary({"a": 1.0}, platform="tpu"))
    cand = _write(tmp_path, "cand.json", _summary({"a": 1.0}, platform="cpu"))
    assert regress.main([base, cand]) == 2
    with pytest.raises(regress.RegressError, match="platform mismatch"):
        regress.check_compatible(
            _summary({}, platform="tpu"), _summary({}, platform="cpu")
        )
    # the override downgrades the rejection to a comparison
    assert regress.main([base, cand, "--allow-platform-mismatch"]) == 0


def test_regress_coverage_changes_warn_not_fail():
    rows, warnings = regress.compare(
        _summary({"kept": 1.0, "dropped": 1.0}),
        _summary({"kept": 1.0, "added": 1.0}),
    )
    assert [r["metric"] for r in rows] == ["kept"]
    assert any("dropped" in w for w in warnings)
    assert any("added" in w for w in warnings)


def test_regress_rejects_non_summary_file(tmp_path):
    bogus = _write(tmp_path, "bogus.json", {"records": []})
    with pytest.raises(regress.RegressError, match="not a BENCH_summary"):
        regress.load_summary(bogus)


# --- bench summary + provenance meta -----------------------------------------


def test_save_results_meta_round_trip(tmp_path, monkeypatch):
    sys.path.insert(0, _REPO)
    try:
        from benchmarks import _common
    finally:
        sys.path.pop(0)
    monkeypatch.setattr(_common, "_REPO", str(tmp_path))
    monkeypatch.setattr(_common, "RUN_DATE", "2026-08-08T00:00:00")
    path = _common.save_results("unit", [{"x": 1}], meta={"extra": "y"})
    with open(path) as f:
        data = json.load(f)
    assert data["records"] == [{"x": 1}]
    meta = data["meta"]
    assert meta["date"] == "2026-08-08T00:00:00"
    assert meta["extra"] == "y"
    # provenance fields the regression gate keys off
    assert meta["platform"] == "cpu" and meta["device_count"] == 1
    assert meta["jax_version"] is not None


def test_save_bench_summary_is_valid_regress_input(tmp_path, monkeypatch):
    sys.path.insert(0, _REPO)
    try:
        from benchmarks import _common
    finally:
        sys.path.pop(0)
    monkeypatch.setattr(_common, "_REPO", str(tmp_path))
    path = _common.save_bench_summary({"m1": 10.0, "m2": 20})
    summary = regress.load_summary(path)  # schema-validates
    assert summary["metrics"] == {"m1": 10.0, "m2": 20.0}
    assert regress.main([path, path]) == 0


def test_bench_runner_worker_path_unaffected():
    """The committed BENCH_summary baseline must stay a valid gate input."""
    path = os.path.join(_REPO, "results", "benchmarks", "BENCH_summary.json")
    summary = regress.load_summary(path)
    assert summary["metrics"], "committed baseline has no metrics"
    assert summary["meta"]["platform"] == "cpu"


# --- report ------------------------------------------------------------------


def test_report_renders_all_sections(tiny_machine, tiny_catalog, tmp_path):
    bench_dir = str(tmp_path / "benchmarks")
    os.makedirs(bench_dir)
    with open(os.path.join(bench_dir, "BENCH_summary.json"), "w") as f:
        json.dump(_summary({"eval_window/x": 100.0}), f)
    md = report_lib.render_markdown(tiny_machine, tiny_catalog, bench_dir, None)
    for kernel in ("gm_eval", "vegas_iterate", "service_dispatch"):
        assert kernel in md
    assert "roofline frac" in md
    assert "eval_window/x" in md
    assert "## Machine" in md and "## Benchmark trajectory" in md
    html = report_lib.render_html(md)
    assert "gm_eval" in html


def test_report_includes_latency_and_idle_from_metrics(
    tiny_machine, tiny_catalog, tmp_path
):
    import numpy as np

    from repro.core.config import QuadratureConfig
    from repro.core.integrands import get_param
    from repro.service import BatchScheduler, QuadRequest
    from repro.telemetry import JsonlSink, Recorder

    family = get_param("genz_gaussian")
    cfg = QuadratureConfig(
        d=2,
        integrand="genz_gaussian",
        rel_tol=1e-4,
        capacity=1 << 9,
        batch_slots=4,
        max_iters=60,
        sync_every=4,
    )
    metrics_path = str(tmp_path / "m.jsonl")
    rec = Recorder(sinks=(JsonlSink(metrics_path),))
    rng = np.random.default_rng(0)
    reqs = [QuadRequest(req_id=i, theta=family.sample_theta(2, rng)) for i in range(5)]
    list(BatchScheduler(cfg, family, recorder=rec).serve(reqs))
    rec.close()

    md = report_lib.render_markdown(
        tiny_machine, tiny_catalog, str(tmp_path / "nobench"), metrics_path
    )
    assert "service.dispatch_wall_s" in md
    assert "idle fraction" in md
    # a real latency table rendered (not the all-dashes empty row)
    dispatch_row = next(
        l for l in md.splitlines() if l.startswith("| service.dispatch_wall_s")
    )
    assert "ms" in dispatch_row


def test_report_cli_writes_both_files(tiny_machine, tiny_catalog, tmp_path):
    machine_path = str(tmp_path / "machine.json")
    catalog_path = str(tmp_path / "catalog.json")
    save_machine(tiny_machine, machine_path)
    catalog_lib.save_catalog(tiny_catalog, catalog_path)
    out = str(tmp_path / "out")
    rc = report_lib.main(
        [
            "--machine",
            machine_path,
            "--catalog",
            catalog_path,
            "--bench-dir",
            str(tmp_path / "nobench"),
            "--out",
            out,
        ]
    )
    assert rc == 0
    assert os.path.exists(os.path.join(out, "PERF_REPORT.md"))
    assert os.path.exists(os.path.join(out, "PERF_REPORT.html"))


# --- CLI smoke (subprocess: the documented invocations actually run) ---------


def test_regress_cli_subprocess(tmp_path):
    base = _write(tmp_path, "base.json", _summary({"a": 100.0}))
    cand = _write(tmp_path, "cand.json", _summary({"a": 150.0}))
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(_REPO, "src")
    proc = subprocess.run(
        [sys.executable, "-m", "repro.perf.regress", base, cand],
        capture_output=True,
        text=True,
        timeout=120,
        cwd=_REPO,
        env=env,
    )
    assert proc.returncode == 1
    assert "FAIL" in proc.stdout
