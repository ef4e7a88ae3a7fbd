"""BENCHMARK.json against the files it names: every cell, configuration,
traffic mix, loop and metric is a file of its own, found by name."""

import os
import re

from kfbench.lib import files

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def test_every_cell_resolves_to_files(bench):
    for w in bench["workloads"]:
        cell = files.cell(bench, w["name"])
        assert os.path.exists(os.path.join(files.ROOT, cell["config_file"]))
        traffic = files.load_traffic(w["traffic"])
        assert hasattr(files.load_module("loops", traffic["loop"]), "run")
        names = {m["name"] for m in cell["end_to_end"]}
        assert "setup_s" in names and len(names) >= 2, w["name"]
        assert cell["per_layer"], w["name"]
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200


def test_every_metric_has_a_reader_and_a_sound_entry(bench):
    e2e = {m["name"] for m in bench["end_to_end"]}
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"]), m["name"]
        assert re.match(r"^[A-Za-z0-9_/%.\-]{1,16}$", m["unit"])
        assert m["better"] in ("lower", "higher")
        assert hasattr(files.load_module("metrics", m["name"]), "read")
    for m in bench["per_layer"]:
        assert m["moves"] in e2e
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    for m in bench["end_to_end"]:
        assert 0 < m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")


def test_per_layer_metrics_sit_in_cells_that_report_what_they_move(bench):
    cells = {w["name"] for w in bench["workloads"]}
    for m in bench["per_layer"]:
        moved = [e for e in bench["end_to_end"] if e["name"] == m["moves"]][0]
        reporting = set(moved.get("workloads", cells))
        assert set(m.get("workloads", reporting)) <= reporting, m["name"]


def test_configurations_state_source_and_departures(bench):
    for c in bench["configs"]:
        cfg = files.load_config(c["name"])
        assert cfg["source"].startswith(c["source"])
        assert cfg["assumed"]
        # every key cut from the source is listed in both places, with
        # the published value kept beside the one that runs
        assert sorted(cfg.get("reduced", {})) == sorted(c["reduced"])
        for key in c["reduced"]:
            assert cfg[key + "_published"] != cfg[key], (c["name"], key)
            assert str(cfg[key + "_published"]) in cfg["reduced"][key]
        files.load_adapter(cfg["family"])
        files.load_reference(cfg["family"])
    at_most_a_quarter = max(1, len(bench["workloads"]) // 4)
    assert sum(w["chips"] == 4 for w in bench["workloads"]) <= at_most_a_quarter


def test_a_pair_of_configuration_and_traffic_stands_once(bench):
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(set(pairs)) == len(pairs)
    # a train cell's traffic states the whole batch; each chip an equal share
    for w in bench["workloads"]:
        tr = files.load_traffic(w["traffic"])
        if tr["loop"] == "train":
            assert tr["global_batch"] % w["chips"] == 0
