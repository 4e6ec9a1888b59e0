"""A cell, configuration, mix and metric are found by name from files
alone, and every name BENCHMARK.json uses has its file."""
import json
import shutil

from bench import registry, run


def test_every_named_part_has_its_file():
    spec = registry.benchmark()
    for c in spec["configs"]:
        assert (registry.ROOT / c["file"]).is_file()
        assert registry.config(c["name"])["name"] == c["name"]
    # every configuration file, the tests' own too, names a block whose
    # file gives the whole contract
    for path in [*(registry.BENCH / "configs").glob("*.json"),
                 *(registry.BENCH / "tests" / "data" / "configs").glob(
                     "*.json")]:
        name = registry.load_json(path)["block"]
        assert (registry.BENCH / "blocks" / f"{name}.py").is_file(), path
        registry.block(name)
    for w in spec["workloads"]:
        assert registry.cell(w["name"])["check"]["logit_gap_limit"] > 0
        registry.traffic(w["traffic"])
    for m in spec["end_to_end"] + spec["per_layer"]:
        if m["name"] != "setup_s":
            assert callable(registry.reader(m["name"]))


def test_dummy_cell_and_metric_from_added_files(tmp_path):
    base = tmp_path / "bench"
    shutil.copytree(registry.BENCH / "tests" / "data", base)
    (base / "metrics").mkdir()
    (base / "cells" / "tiny.dummy.json").write_text(json.dumps(
        {"loop": "open", "rate_rps": 1.0, "tail_s": 1,
         "check": {"tokens": 1, "logit_gap_limit": 1.0}}))
    (base / "metrics" / "dummy_metric.py").write_text(
        "def read(ctx):\n    return 2 * ctx['x']\n")
    (base / "metrics" / "silent_metric.py").write_text(
        "def read(ctx):\n    return None\n")
    spec = {
        "workloads": [{"name": "tiny.dummy", "config": "tiny",
                       "traffic": "tiny", "chips": 1, "why": "test"}],
        "end_to_end": [{"name": "setup_s", "unit": "s"}],
        "per_layer": [
            {"name": "dummy_metric", "unit": "count",
             "workloads": ["tiny.dummy"]},
            {"name": "silent_metric", "unit": "count"},
            {"name": "other_cells_metric", "unit": "count",
             "workloads": ["somewhere.else"]}],
    }
    wl = registry.workload("tiny.dummy", spec)
    assert registry.cell("tiny.dummy", base)["rate_rps"] == 1.0
    assert registry.config(wl["config"], base)["hidden_size"] == 64
    assert registry.traffic(wl["traffic"], base)["sizes_seed"] == 7
    got = run.report(spec, "tiny.dummy", True, {"x": 21}, 5.0, base)
    assert got == {"dummy_metric": {"value": 42, "unit": "count"}}
    assert run.report(spec, "tiny.dummy", False, {}, 5.0, base) == {
        "setup_s": {"value": 5.0, "unit": "s"}}
