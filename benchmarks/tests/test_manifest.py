import os
import re

import pytest

from benchmarks.harness import manifest

M = manifest.benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def test_exactly_the_contracts_keys():
    assert set(M) == {"command", "paths", "run_seconds", "configs", "workloads",
                      "end_to_end", "per_layer"}
    assert 1 <= M["run_seconds"] <= 51
    runs = 2 + 14 * 24
    assert runs * (M["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


def test_names_and_units_use_only_the_allowed_characters():
    names = [x["name"] for k in ("configs", "workloads", "end_to_end", "per_layer") for x in M[k]]
    names += [w[k] for w in M["workloads"] for k in ("config", "traffic")]
    for n in names:
        assert NAME.match(n), n
    for k in ("end_to_end", "per_layer"):
        assert len({x["name"] for x in M[k]}) == len(M[k])
        for x in M[k]:
            assert UNIT.match(x["unit"]), x
            assert x["better"] in ("lower", "higher")
            assert x["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
    for w in M["workloads"] + M["configs"]:
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"] and "\t" not in w["why"]
    four = sum(1 for w in M["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(M["workloads"]) // 4)


def test_bounds():
    for e in M["end_to_end"]:
        assert 0.01 <= e["bound"] <= 0.1
        assert e["source"] in ("host_clock", "device_trace")
    assert any(e["name"] == "setup_s" for e in M["end_to_end"])


@pytest.mark.parametrize("workload", [w["name"] for w in M["workloads"]])
def test_every_cell_finds_its_files_and_reports_enough(workload):
    cell = manifest.Cell(workload)
    assert cell.config["name"] == cell.workload["config"]
    assert cell.traffic["kind"] in ("train", "serve-open", "serve-closed")
    # families/<model>/<path>.py is there with every function of its path's
    # interface: manifest.family names a missing file or function.
    assert cell.family is manifest.family(cell.config["model"], cell.path)
    reported = {e["name"] for e in cell.end_to_end}
    assert "setup_s" in reported and len(reported) >= 2
    assert cell.per_layer, "a cell reports at least one per-layer metric"
    for p in cell.per_layer:
        spec = manifest.layer_metric(p["name"])
        assert callable(manifest.reader(spec["reader"]))


@pytest.mark.parametrize("metric", [p["name"] for p in M["per_layer"]])
def test_every_per_layer_metric_moves_a_metric_its_cells_report(metric):
    p = {x["name"]: x for x in M["per_layer"]}[metric]
    e2e = {e["name"]: e for e in M["end_to_end"]}
    assert p["moves"] in e2e and p["moves"] != "setup_s"
    all_cells = [w["name"] for w in M["workloads"]]
    moved_in = e2e[p["moves"]].get("workloads", all_cells)
    for cell in p.get("workloads", moved_in):
        assert cell in moved_in, f"{metric} listed in {cell}, which does not report {p['moves']}"
    assert os.path.exists(os.path.join(manifest.BENCH_DIR, "layer_metrics", metric + ".json"))


def test_configs_are_used_and_their_files_lie_under_paths():
    used = {w["config"] for w in M["workloads"]}
    for c in M["configs"]:
        assert c["name"] in used
        assert any(c["file"].startswith(p + "/") for p in M["paths"])
        assert os.path.exists(os.path.join(manifest.ROOT, c["file"]))


def test_a_reader_that_finds_nothing_returns_nothing():
    for p in M["per_layer"]:
        spec = manifest.layer_metric(p["name"])
        assert manifest.reader(spec["reader"])({}, **spec.get("args", {})) is None


def test_a_missing_family_file_or_function_is_named(tmp_path, monkeypatch):
    with pytest.raises(FileNotFoundError, match="benchmarks/families/no_such_model/serve.py"):
        manifest.family("no_such_model", "serve")
    # The transformer has no train path yet: a new FILE, not an edit.
    with pytest.raises(FileNotFoundError, match="families/transformer/train.py"):
        manifest.family("transformer", "train")
    family_dir = tmp_path / "families" / "half"
    family_dir.mkdir(parents=True)
    (family_dir / "serve.py").write_text("def build(config, overrides=None): ...\nmax_len = 7\n")
    monkeypatch.setattr(manifest, "BENCH_DIR", str(tmp_path))
    with pytest.raises(AttributeError, match="lacks apply_fn, decode_fns, max_len, token_vocab"):
        manifest.family("half", "serve")
