"""The checker passes the real manifest and refuses each doctored one."""

import copy
import json
import os
import shutil

import pytest

from benchmark import check_manifest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def real():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.fixture
def tree(tmp_path):
    """A copy of the manifest and of the files it names."""
    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))

    def write(manifest):
        (tmp_path / "BENCHMARK.json").write_text(
            manifest if isinstance(manifest, str) else json.dumps(manifest))
        return check_manifest.check(str(tmp_path))
    return write


def test_real_manifest_passes():
    assert check_manifest.check(ROOT) == []


def test_copy_passes(tree):
    assert tree(real()) == []


def cell(m, i=0):
    return m["workloads"][i]


def metric(m, group, name):
    return next(x for x in m[group] if x["name"] == name)


def add_cell(m, **over):
    m["workloads"].append(dict(copy.deepcopy(cell(m)), **over))


DOCTORED = {
    "name_with_space": lambda m: cell(m).update(name="a b"),
    "name_too_long": lambda m: cell(m).update(name="x" * 65),
    "name_starts_with_dot": lambda m: cell(m).update(name=".cell"),
    "name_starts_with_dash": lambda m: m["configs"][0].update(name="-c"),
    "metric_name_with_slash": lambda m: m["per_layer"][0].update(name="a/b"),
    "traffic_name_with_comma": lambda m: cell(m).update(traffic="a,b"),
    "unit_with_space": lambda m: m["end_to_end"][0].update(
        unit="tokens per s"),
    "unit_too_long": lambda m: m["end_to_end"][0].update(unit="u" * 17),
    "unit_greek": lambda m: m["per_layer"][0].update(unit="μs"),
    "source_is_a_list": lambda m: m["configs"][0].update(
        source=["a paper", "a url"]),
    "source_is_an_object": lambda m: m["configs"][0].update(
        source={"paper": "x"}),
    "source_too_long": lambda m: m["configs"][0].update(source="s" * 201),
    "source_empty": lambda m: m["configs"][0].update(source=""),
    "source_with_newline": lambda m: m["configs"][0].update(source="a\nb"),
    "source_with_tab": lambda m: m["configs"][0].update(source="a\tb"),
    "source_multiplication_sign": lambda m: m["configs"][0].update(
        source="24 × 1024"),
    "why_with_arrow": lambda m: cell(m).update(why="a → b"),
    "bound_over_limit": lambda m: m["end_to_end"][0].update(bound=0.2),
    "bound_under_one_percent": lambda m: m["end_to_end"][0].update(
        bound=0.001),
    "bound_absolute": lambda m: m["end_to_end"][0].update(bound="5 ms"),
    "setup_bound_over_limit": lambda m: metric(
        m, "end_to_end", "setup_s").update(bound=0.5),
    "no_setup_s": lambda m: m["end_to_end"].remove(
        metric(m, "end_to_end", "setup_s")),
    "e2e_from_program_counter": lambda m: m["end_to_end"][0].update(
        source="program_counter"),
    "layer_source_unknown": lambda m: m["per_layer"][0].update(source="x"),
    "moves_unknown_metric": lambda m: m["per_layer"][0].update(moves="nope"),
    "moves_metric_of_another_cell": lambda m: (
        add_cell(m, name="other", traffic="chat-closed"
                 if cell(m)["traffic"] != "chat-closed" else "train-steady"),
        m["per_layer"][0].pop("workloads", None)),
    "metric_in_unknown_cell": lambda m: m["per_layer"][0].update(
        workloads=["nope"]),
    "extra_key_on_metric": lambda m: m["per_layer"][0].update(why="because"),
    "extra_key_on_cell": lambda m: cell(m).update(note="x"),
    "missing_key_on_config": lambda m: m["configs"][0].pop("why"),
    "extra_top_level_key": lambda m: m.update(notes="x"),
    "too_many_four_chip_cells": lambda m: [
        add_cell(m, name=f"four{i}", traffic=f"t{i}", chips=4)
        for i in range(2)],
    "chips_two": lambda m: cell(m).update(chips=2),
    "config_without_cell": lambda m: m["configs"].append(dict(
        m["configs"][0], name="unused",
        file="benchmark/configs/unused.json")),
    "config_file_missing": lambda m: m["configs"][0].update(
        file="benchmark/configs/nope.json"),
    "config_file_outside_paths": lambda m: m["configs"][0].update(
        file="bench.py"),
    "traffic_file_missing": lambda m: cell(m).update(traffic="nope"),
    "reader_file_missing": lambda m: m["per_layer"][0].update(name="nope"),
    "reduced_names_a_width": lambda m: m["configs"][0].update(
        reduced=["d_model"]),
    "reduced_names_a_dim": lambda m: m["configs"][0].update(
        reduced=["kv_lora_rank"]),
    "run_seconds_too_short": lambda m: m.update(run_seconds=5),
    "run_seconds_too_long": lambda m: m.update(run_seconds=52),
    "run_seconds_fraction": lambda m: m.update(run_seconds=30.5),
    "duplicate_cell": lambda m: add_cell(m),
    "duplicate_metric": lambda m: m["per_layer"].append(
        copy.deepcopy(m["per_layer"][0])),
    "duplicate_pair": lambda m: add_cell(m, name="same-pair"),
    "command_absolute_path": lambda m: m.update(
        command=["/usr/bin/python3", "-m", "benchmark.run"]),
    "path_leaves_repo": lambda m: m.update(paths=["../benchmark"]),
    "reader_disagrees_on_layer": lambda m: m["per_layer"][0].update(
        layer="another layer"),
    "reader_disagrees_on_unit": lambda m: m["per_layer"][0].update(unit="s"),
    "better_sideways": lambda m: m["per_layer"][0].update(better="same"),
    "cell_without_layer_metric": lambda m: [
        x.update(workloads=[w for w in x.get(
            "workloads", [c["name"] for c in m["workloads"]])
            if w != cell(m)["name"]] or ["nope"])
        for x in m["per_layer"]],
}


@pytest.mark.parametrize("case", sorted(DOCTORED))
def test_doctored_manifest_is_refused(tree, case):
    m = real()
    DOCTORED[case](m)
    assert tree(m), f"{case} was not refused"


def test_command_file_outside_paths_is_refused(tmp_path, tree):
    m = real()
    m["command"] = ["python3", "bench.py"]
    (tmp_path / "bench.py").write_text("")
    assert any("outside paths" in p for p in tree(m))


def test_non_ascii_in_a_config_file_is_refused(tmp_path, tree):
    m = real()
    path = tmp_path / m["configs"][0]["file"]
    path.write_text(path.read_text().replace("GPT-2", "GPT‑2"),
                    encoding="utf-8")
    assert any("ASCII" in p for p in tree(m))


def test_non_ascii_in_a_traffic_file_is_refused(tmp_path, tree):
    m = real()
    path = tmp_path / "benchmark" / "traffic" / (
        m["workloads"][0]["traffic"] + ".json")
    path.write_text(path.read_text().replace("steps", "stéps"),
                    encoding="utf-8")
    assert any("ASCII" in p for p in tree(m))


def test_a_file_with_a_space_in_its_name_is_refused(tmp_path, tree):
    (tmp_path / "benchmark" / "traffic" / "my mix.json").write_text("{}")
    assert any("file name" in p for p in tree(real()))


def test_not_json_is_refused(tree):
    assert tree("{not json")


def test_oversized_manifest_is_refused(tree):
    m = real()
    m["workloads"][0]["why"] = "w" * 200
    text = json.dumps(m) + " " * (64 * 1024)
    assert any("bytes" in p for p in tree(text))
