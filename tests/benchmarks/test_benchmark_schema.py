"""BENCHMARK.json against the contract's schema, and the files it names."""

import importlib.util
import json
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
WIDTH = re.compile(r"(hidden|intermediate|latent|state|proj|_dim$|_rank$|"
                   r"head_size|head_dim|expan|experts_per|n_embd|n_inner)")

CELLS = {w["name"]: w for w in BENCH["workloads"]}
CONFIGS = {c["name"]: c for c in BENCH["configs"]}
E2E = {m["name"]: m for m in BENCH["end_to_end"]}
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]


def _cells_of(metric):
    return metric.get("workloads", list(CELLS))


def test_top_level_keys_and_limits():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 65536
    assert isinstance(BENCH["run_seconds"], int)
    assert 1 <= BENCH["run_seconds"] <= 51
    assert 1 <= len(BENCH["paths"]) <= 16
    assert 1 <= len(BENCH["command"]) <= 32
    assert 1 <= len(BENCH["configs"]) <= 24
    assert 1 <= len(BENCH["workloads"]) <= 24
    assert 1 <= len(BENCH["end_to_end"]) <= 16
    assert 1 <= len(BENCH["per_layer"]) <= 128


def test_a_full_check_of_24_cells_fits_the_budget():
    runs = 2 + 14 * 24
    seconds = runs * (BENCH["run_seconds"] + 60) + 24 * 2 * 90 + 1200
    assert seconds <= 43200


def test_paths_and_command():
    for p in BENCH["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
        assert os.path.isdir(os.path.join(ROOT, p))
    for word in BENCH["command"]:
        assert 1 <= len(word) <= 200 and "\n" not in word and "\t" not in word
        assert not word.startswith("/") and ".." not in word
    script = BENCH["command"][1]
    assert any(script.startswith(p + "/") for p in BENCH["paths"])
    assert os.path.isfile(os.path.join(ROOT, script))


def test_names_are_unique():
    for group in (BENCH["configs"], BENCH["workloads"], METRICS):
        names = [x["name"] for x in group]
        assert len(names) == len(set(names))
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))


def test_the_four_chip_quota():
    four = [w for w in BENCH["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(BENCH["workloads"]) // 4)


@pytest.mark.parametrize("config", BENCH["configs"], ids=lambda c: c["name"])
def test_config_entry(config):
    assert set(config) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(config["name"])
    assert config["source"].startswith("http") or "arXiv" in config["source"]
    for key in ("source", "why"):
        assert 1 <= len(config[key]) <= 200 and "\n" not in config[key]
    assert any(config["file"].startswith(p + "/") for p in BENCH["paths"])
    assert PATH.match(config["file"])
    assert len(config["reduced"]) <= 16
    for key in config["reduced"]:
        assert NAME.match(key) and not WIDTH.search(key)
    assert any(w["config"] == config["name"] for w in BENCH["workloads"])
    with open(os.path.join(ROOT, config["file"])) as f:
        body = json.load(f)
    assert body["name"] == config["name"]
    assert body["source"] == config["source"]
    assert body["reduced"] == config["reduced"]
    assert "assumed" in body and "tolerance" in body and "builder" in body
    assert "why" in body["tolerance"]
    bdir = os.path.dirname(os.path.dirname(config["file"]))
    for sub in ("reference", "flops"):
        assert os.path.isfile(os.path.join(ROOT, bdir, sub,
                                           config["name"] + ".py"))
    assert os.path.isfile(os.path.join(ROOT, bdir, "builders",
                                       body["builder"] + ".py"))


def test_config_files_are_distinct():
    files = [c["file"] for c in BENCH["configs"]]
    assert len(files) == len(set(files))


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda w: w["name"])
def test_cell_entry_and_its_files(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    for key in ("name", "config", "traffic"):
        assert NAME.match(cell[key])
    assert cell["name"] == f"{cell['config']}.{cell['traffic']}"
    assert cell["chips"] in (1, 4)
    assert 1 <= len(cell["why"]) <= 200 and "\n" not in cell["why"]
    assert cell["config"] in CONFIGS
    bdir = os.path.dirname(os.path.dirname(CONFIGS[cell["config"]]["file"]))
    path = os.path.join(ROOT, bdir, "traffic", cell["traffic"] + ".json")
    with open(path) as f:
        mix = json.load(f)
    assert mix["name"] == cell["traffic"] and mix["chips"] == cell["chips"]
    assert os.path.isfile(os.path.join(ROOT, bdir, "drivers",
                                       mix["kind"] + ".py"))


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_cell_reports_setup_another_end_to_end_and_a_layer_metric(cell):
    e2e = [m["name"] for m in BENCH["end_to_end"] if cell in _cells_of(m)]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert any(cell in _cells_of(m) for m in BENCH["per_layer"])


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_metric_entry(metric):
    per_layer = metric in BENCH["per_layer"]
    want = {"name", "unit", "better", "source"} | (
        {"layer", "moves"} if per_layer else {"bound"})
    assert set(metric) - {"workloads"} == want
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in SOURCES
    for cell in metric.get("workloads", []):
        assert cell in CELLS
    if per_layer:
        assert 1 <= len(metric["layer"]) <= 200
        assert "\n" not in metric["layer"] and "\t" not in metric["layer"]
    else:
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.1
    if "roofline" in metric["name"] or "mfu" in metric["name"]:
        assert metric["unit"] == "%"


def test_setup_s_is_an_end_to_end_metric_of_every_cell():
    assert "workloads" not in E2E["setup_s"]
    assert E2E["setup_s"]["bound"] <= 0.1


@pytest.mark.parametrize("metric", BENCH["per_layer"],
                         ids=lambda m: m["name"])
def test_moves_is_reported_wherever_the_metric_is(metric):
    assert metric["moves"] in E2E
    moved = set(_cells_of(E2E[metric["moves"]]))
    assert set(_cells_of(metric)) <= moved


@pytest.mark.parametrize("metric", BENCH["per_layer"],
                         ids=lambda m: m["name"])
def test_every_per_layer_metric_has_its_own_reader(metric):
    bdir = os.path.dirname(os.path.dirname(BENCH["configs"][0]["file"]))
    path = os.path.join(ROOT, bdir, "layer_metrics", metric["name"] + ".py")
    spec = importlib.util.spec_from_file_location("reader", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    # the file declares what it is; which cells report it is
    # BENCHMARK.json's alone to say, so a later cell needs no edit here
    assert "workloads" not in mod.METRIC
    assert mod.METRIC == {k: v for k, v in metric.items()
                          if k != "workloads"}
    assert mod.read({}) is None            # nothing to read: nothing
    assert mod.__doc__


def test_every_reader_file_is_listed():
    bdir = os.path.dirname(os.path.dirname(BENCH["configs"][0]["file"]))
    files = {f[:-3] for f in os.listdir(os.path.join(ROOT, bdir,
                                                     "layer_metrics"))
             if f.endswith(".py")}
    assert files == {m["name"] for m in BENCH["per_layer"]}


def test_layers_are_perf_mds():
    with open(os.path.join(ROOT, "PERF.md")) as f:
        perf = f.read()
    for layer in {m["layer"] for m in BENCH["per_layer"]}:
        assert f"| {layer} |" in perf, layer


def test_peaks_name_their_source():
    with open(os.path.join(ROOT, "benchmarks", "peaks.json")) as f:
        peaks = json.load(f)
    assert peaks["source"]
    v5e = peaks["device_kinds"]["TPU v5 lite"]
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
