"""Finding configurations, traffic, limits and metric readers by name, and
the shape of ``BENCHMARK.json``. A cell or a metric that a later change
adds is only added files and entries: shown here with a dummy of each in a
copy of the tree, with no file of the benchmark edited."""
import json
import re
import shutil

import pytest

from chipbench import base, spec

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.fixture(scope="module")
def bench():
    return json.loads((spec.CHECKOUT / "BENCHMARK.json").read_text())


def test_every_cell_loads_by_name(bench):
    for w in bench["workloads"]:
        cell = spec.load_cell(w["name"])
        assert cell.config["name"] == w["config"]
        assert issubclass(spec.loop_class(cell.traffic["loop"]), base.Loop)
        assert cell.limits["numbers"]
        names = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2
        for m in cell.end_to_end:
            assert callable(spec.reader("end_to_end", m["name"]))
        assert cell.per_layer
        for m in cell.per_layer:
            assert callable(spec.metric_reader(m["name"]))
            assert m["moves"] in names


def test_names_units_and_files(bench):
    assert bench["paths"] == ["benchmarks/chip"]
    assert bench["command"][1] == "benchmarks/chip/run.py"
    metrics = bench["end_to_end"] + bench["per_layer"]
    for m in metrics:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    assert len({m["name"] for m in metrics}) == len(metrics)
    cells = {w["name"] for w in bench["workloads"]}
    for m in metrics:
        assert set(m.get("workloads", cells)) <= cells
    for c in bench["configs"]:
        assert (spec.CHECKOUT / c["file"]).is_file()
        assert any(w["config"] == c["name"] for w in bench["workloads"])
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25


def test_kernel_roofline_and_mfu_names(bench):
    names = {m["name"] for m in bench["per_layer"]}
    assert "scan_roofline" in names
    assert {"ingest.mfu", "query.mfu"} <= names


def test_config_matches_the_registered_architecture():
    from repro.configs.base import get_arch
    cfg = spec._load_json(spec.BENCH_DIR / "configs" /
                          "recall-imagebind.json")
    mine, reg = spec.arch_spec(cfg), get_arch("recall-imagebind")
    assert mine.model == reg.model and mine.recall == reg.recall
    assert mine.shape("query_batch").global_batch == 64


def test_clip_config_widths():
    cfg = spec._load_json(spec.BENCH_DIR / "configs" / "clip-vit-l14.json")
    s = spec.arch_spec(cfg)
    v, t = s.model.tower("vision"), s.model.tower("text")
    assert (v.n_layers, v.d_model, v.n_heads, v.d_ff, v.n_tokens,
            v.d_input) == (24, 1024, 16, 4096, 256, 588)
    assert (t.n_layers, t.d_model, t.n_heads, t.d_ff, t.n_tokens,
            t.vocab) == (12, 768, 12, 3072, 77, 49408)
    assert s.model.embed_dim == 768
    assert spec.query_granularities(cfg, 12) == [4, 8, 12]
    assert spec.query_granularities(
        spec._load_json(spec.BENCH_DIR / "configs" /
                        "recall-imagebind.json"), 24) == [4, 12, 24]


def test_peaks_are_keyed_by_device_kind():
    p = spec.load_peaks("TPU v5 lite")
    assert p["bf16_flops"] == 1.97e14 and p["int8_ops"] == 3.93e14
    assert p["hbm_bytes_per_s"] == 8.19e11
    with pytest.raises(spec.SpecError):
        spec.load_peaks("cpu")


def test_a_new_cell_and_metric_are_added_files_only(tmp_path, bench):
    """Copy the tree, add a dummy configuration, traffic, limits and
    metric, and one entry each to BENCHMARK.json: the harness finds them,
    and every file that was there is byte for byte unchanged."""
    root = tmp_path
    shutil.copytree(spec.BENCH_DIR, root / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in
              (root / "benchmarks" / "chip").rglob("*") if p.is_file()}
    b = root / "benchmarks" / "chip"
    cfg = json.loads((b / "configs" / "clip-vit-l14.json").read_text())
    cfg["name"] = "dummy-model"
    (b / "configs" / "dummy-model.json").write_text(json.dumps(cfg))
    (b / "traffic" / "dummy-mix.json").write_text(json.dumps(
        {"loop": "query", "batch": 8, "k": 10, "bank_rows": 4096,
         "filler_norm": 1.0, "placed_fine": 0, "placed_fresh": 0,
         "warmup_drains": 2, "filler_check_rows": 16}))
    (b / "limits" / "dummy.cell.json").write_text(json.dumps(
        {"numbers": {"query_gap": {"limit": 0.1}}}))
    (b / "metrics" / "dummy.metric.py").write_text(
        "def read(ctx):\n    return 42.0\n")
    new = dict(bench)
    new["configs"] = bench["configs"] + [
        {"name": "dummy-model", "source": "test",
         "file": "benchmarks/chip/configs/dummy-model.json", "reduced": [],
         "why": "test"}]
    new["workloads"] = bench["workloads"] + [
        {"name": "dummy.cell", "config": "dummy-model",
         "traffic": "dummy-mix", "chips": 1, "why": "test"}]
    new["per_layer"] = bench["per_layer"] + [
        {"name": "dummy.metric", "unit": "%", "better": "higher",
         "source": "host_clock", "layer": "device",
         "moves": "queries_per_s", "workloads": ["dummy.cell"]}]
    (root / "BENCHMARK.json").write_text(json.dumps(new))
    cell = spec.load_cell("dummy.cell", root=root)
    assert cell.config["name"] == "dummy-model"
    assert cell.traffic["bank_rows"] == 4096
    assert [m["name"] for m in cell.per_layer][-1] == "dummy.metric"
    assert spec.metric_reader("dummy.metric", bench_dir=b)({}) == 42.0
    # a metric without a workloads key reaches the new cell as well
    assert "setup_s" in {m["name"] for m in cell.end_to_end}
    for p, data in before.items():
        assert p.read_bytes() == data, p


def test_unknown_names_are_refused(tmp_path):
    with pytest.raises(spec.SpecError):
        spec.load_cell("no.such.cell")
    with pytest.raises(spec.SpecError):
        spec.metric_reader("no.such.metric")
    with pytest.raises(spec.SpecError):
        spec.loop_class("no.such.loop")
    with pytest.raises(spec.SpecError):
        spec.reader("end_to_end", "no.such.metric")
