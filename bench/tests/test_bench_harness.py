"""The harness's data: every cell of ``BENCHMARK.json`` finds its
configuration, traffic mix, entry driver and per-layer readers by name,
the file keeps the contract's shape, and ``bench/run.py`` refuses to run
without a TPU."""
import json
import os
import re

import pytest

from bench import harness

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
TEXT = re.compile(r"^[^\t\n\r]{1,200}$")
SPEC = harness.spec()


def test_top_level_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["paths"] == ["bench"]
    assert 1 <= SPEC["run_seconds"] <= 51
    assert len(json.dumps(SPEC)) < 64 * 1024
    for word in SPEC["command"]:
        assert TEXT.match(word) and not word.startswith("/")
        assert ".." not in word


@pytest.mark.parametrize("entry", SPEC["configs"], ids=lambda c: c["name"])
def test_config_file(entry):
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(entry["name"])
    assert TEXT.match(entry["source"]) and TEXT.match(entry["why"])
    assert entry["file"] == f"bench/configs/{entry['name']}.json"
    cfg = harness.load_json(harness.config_path(entry["name"]))
    assert cfg["name"] == entry["name"]
    assert cfg["source"] == entry["source"]
    assert cfg["reduced"] == entry["reduced"]
    assert all(NAME.match(k) for k in entry["reduced"])
    assert any(w["config"] == entry["name"] for w in SPEC["workloads"])


@pytest.mark.parametrize("wl", SPEC["workloads"], ids=lambda w: w["name"])
def test_workload_finds_its_files(wl):
    assert set(wl) == {"name", "config", "traffic", "chips", "why"}
    for key in ("name", "config", "traffic"):
        assert NAME.match(wl[key])
    assert TEXT.match(wl["why"]) and wl["chips"] in (1, 4)
    _, _, config, mix = harness.cell(wl["name"])
    assert isinstance(harness.driver(mix["entry"]), type)
    assert set(mix["limits"]) and all(
        isinstance(v, (int, float)) for v in mix["limits"].values())
    e2e, layer = harness.metrics_of(SPEC, wl["name"])
    names = [m["name"] for m in e2e]
    assert "setup_s" in names and len(names) >= 2
    assert layer
    for m in layer:
        assert callable(harness.reader(m["name"]))


def test_metrics_shape():
    e2e_names = {m["name"] for m in SPEC["end_to_end"]}
    cells = {w["name"] for w in SPEC["workloads"]}
    seen = set()
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["name"] not in seen
        seen.add(m["name"])
        assert set(m.get("workloads", [])) <= cells
    for m in SPEC["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in SPEC["per_layer"]:
        assert TEXT.match(m["layer"]) and m["moves"] in e2e_names
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert os.path.exists(harness.layer_path(m["name"]))
        for w in m["workloads"]:
            e2e, _ = harness.metrics_of(SPEC, w)
            assert m["moves"] in {x["name"] for x in e2e}


def test_peaks_by_device_kind():
    v5e = harness.peaks("TPU v5 lite")
    assert v5e["bf16_flop_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9 and v5e["source"]
    with pytest.raises(harness.BenchError, match="no peaks"):
        harness.peaks("cpu")


@pytest.mark.parametrize("entry", ["simulate", "pack"])
def test_builtin_entry_from_its_file(entry):
    from bench import drivers

    path = harness.entry_path(entry)
    assert path.endswith(os.path.join("bench", "entries", f"{entry}.py"))
    assert os.path.isfile(path)
    assert harness.driver(entry) is getattr(drivers, entry.capitalize())


def test_missing_entry_names_its_path():
    with pytest.raises(harness.BenchError) as e:
        harness.driver("no-such-entry")
    assert harness.entry_path("no-such-entry") in str(e.value)
    assert "'pack'" in str(e.value) and "'simulate'" in str(e.value)


X4_METRICS = ("eval_rate", "setup_s", "host_ms.sim", "device_us_per_step.sim",
              "idle.sim", "compile_s", "read_ms.sim", "post_ms.sim")


def test_four_chip_cell_loads():
    _, wl, config, mix = harness.cell("omb100-sim-packers-x4")
    assert wl["chips"] == 4 and wl["config"] == "omb-100p"
    assert mix["entry"] == "simulate"
    b = len(mix["families"]) * mix["groups_per_family"]
    assert b == 256 and b % wl["chips"] == 0
    assert (mix["steps"], mix["fleets"]) == (100, 4)
    e2e, layer = harness.metrics_of(SPEC, wl["name"])
    reported = e2e + layer
    assert sorted(m["name"] for m in reported) == sorted(X4_METRICS)
    for m in reported:
        assert wl["name"] in m.get("workloads", [wl["name"]])


def test_unknown_workload_is_named():
    with pytest.raises(harness.BenchError, match="no workload"):
        harness.cell("no-such-cell")


def test_run_without_a_tpu_exits_nonzero(capsys):
    from bench import run

    rc = run.main(["--workload", SPEC["workloads"][0]["name"], "--seed", "1",
                   "--seconds", "1", "--trace", "0"])
    out, err = capsys.readouterr()
    assert rc != 0
    assert out == ""
    assert "needs a TPU" in err
