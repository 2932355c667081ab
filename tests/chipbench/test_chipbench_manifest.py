"""BENCHMARK.json against the rules it has to keep, and against the
files it names: every configuration, mix and reader is found by name."""

import json
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
WIDTHS = re.compile(r"(hidden|intermediate|latent|state|proj).*size"
                    r"|_dim$|_rank$|head_dim|expansion|experts_per_tok")


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def cells_of(metric, bench):
    return metric.get("workloads", [w["name"] for w in bench["workloads"]])


def test_top_level_keys_and_limits(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer",
                          "trace_in_run"}
    assert bench["trace_in_run"] is True  # run.py takes --trace 2
    assert bench["paths"] == ["chipbench", "tests/chipbench"]
    assert isinstance(bench["run_seconds"], int)
    assert 10 <= bench["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) < 65536
    program = bench["command"][1]
    assert any(program.startswith(p + "/") for p in bench["paths"])
    assert os.path.isfile(os.path.join(REPO, program))


def test_names_units_and_keys(bench):
    metrics = bench["end_to_end"] + bench["per_layer"]
    names = ([m["name"] for m in metrics]
             + [w["name"] for w in bench["workloads"]]
             + [c["name"] for c in bench["configs"]]
             + [w["traffic"] for w in bench["workloads"]]
             + [k for c in bench["configs"] for k in c["reduced"]])
    for name in names:
        assert NAME.match(name), name
    for group in (metrics, bench["workloads"], bench["configs"]):
        own = [e["name"] for e in group]
        assert len(own) == len(set(own)), own
    for m in metrics:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher"), m
        assert m["source"] in SOURCES, m
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "bound", "source"}, m
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "source", "layer", "moves"}, m
        assert 1 <= len(m["layer"]) <= 200 and "\n" not in m["layer"]
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200, w
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert 1 <= len(c["source"]) <= 200 and len(c["reduced"]) <= 16


def test_every_metric_has_cells_that_report_what_it_moves(bench):
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    known = {w["name"] for w in bench["workloads"]}
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert set(cells_of(m, bench)) <= known, m
    for m in bench["per_layer"]:
        moved = e2e[m["moves"]]
        assert set(cells_of(m, bench)) <= set(cells_of(moved, bench)), m
    for w in bench["workloads"]:
        reported = [m["name"] for m in bench["end_to_end"]
                    if w["name"] in cells_of(m, bench)]
        assert "setup_s" in reported and len(reported) >= 2, w
        assert any(w["name"] in cells_of(m, bench)
                   for m in bench["per_layer"]), w


def test_what_moves_setup_s_happens_in_set_up(bench):
    """The resume and its parts name ``setup_s``: true only where the
    mix puts the kill, the restart and the restore before the window."""
    traffic = {}
    for w in bench["workloads"]:
        with open(os.path.join(REPO, "chipbench", "traffic",
                               w["traffic"] + ".json")) as f:
            traffic[w["name"]] = json.load(f)
    moved = [m for m in bench["per_layer"] if m["moves"] == "setup_s"]
    assert {m["name"] for m in moved} >= {"resume_s", "detect_s",
                                          "restore_s", "boot_s"}
    # the measured worker boots in set-up under every mix: the parts of
    # its boot that the program's own events give are read in every cell
    booted = {"boot_import_s", "boot_backend_s", "boot_build_s"}
    known = {w["name"] for w in bench["workloads"]}
    for m in moved:
        if m["name"] in booted:
            assert set(cells_of(m, bench)) == known, m["name"]
            continue
        for cell in cells_of(m, bench):
            assert traffic[cell]["kill"] == "in_setup", (m["name"], cell)


def test_every_layer_is_one_of_perf_md_and_every_reader_a_file(bench):
    """A metric's ``layer`` is, letter for letter, a layer of the table
    in ``PERF.md`` section 3 (the words before the modules in brackets),
    and that table names every metric beside what it reads."""
    with open(os.path.join(REPO, "PERF.md")) as f:
        perf = f.read()
    section = perf.split("\n## 3. Layers", 1)[1].split("\n## 4.", 1)[0]
    rows = [[c.strip() for c in line.strip().strip("|").split("|")]
            for line in section.splitlines() if line.startswith("|")]
    layers = {row[0].split(" (")[0] for row in rows if row[0]}
    named = {found.group(1) for row in rows if len(row) > 1
             for found in [re.match(r"`([^`]+)`", row[1])] if found}
    for m in bench["per_layer"]:
        assert m["layer"] in layers, (m["name"], m["layer"], layers)
        assert m["name"] in named, m["name"]
        reader = os.path.join(REPO, "chipbench", "layer_metrics",
                              m["name"] + ".py")
        with open(reader) as f:
            assert "def read(ctx):" in f.read(), reader


def test_cells_and_chips(bench):
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(pairs) == len(set(pairs))
    four = [w for w in bench["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(bench["workloads"]) // 4)
    used = {w["config"] for w in bench["workloads"]}
    assert used == {c["name"] for c in bench["configs"]}


def test_files_are_found_by_name(bench):
    files = [c["file"] for c in bench["configs"]]
    assert len(files) == len(set(files))
    for c in bench["configs"]:
        assert c["file"] == f"chipbench/configs/{c['name']}.json"
        with open(os.path.join(REPO, c["file"])) as f:
            model = json.load(f)
        assert model["source"] == c["source"]
        assert set(c["reduced"]) == set(model["reduced"])
        assert not any(WIDTHS.search(k) for k in c["reduced"]), c
        cells = [w for w in bench["workloads"] if w["config"] == c["name"]]
        assert all(w["chips"] == model["chips"] for w in cells)
    for w in bench["workloads"]:
        assert os.path.isfile(os.path.join(
            REPO, "chipbench", "traffic", w["traffic"] + ".json")), w
    for m in bench["per_layer"]:
        assert os.path.isfile(os.path.join(
            REPO, "chipbench", "layer_metrics", m["name"] + ".py")), m


def test_configurations_keep_the_published_widths(bench):
    """Mistral-7B-v0.3's config.json, as ISSUE 24 quotes it; only the
    depth is cut."""
    published = {
        "hidden_size": 4096, "intermediate_size": 14336,
        "num_attention_heads": 32, "num_key_value_heads": 8,
        "head_dim": 128, "vocab_size": 32768, "rope_theta": 1000000.0,
        "rms_norm_eps": 1e-05, "max_position_embeddings": 32768,
        "sliding_window": None, "tie_word_embeddings": False,
    }
    for c in bench["configs"]:
        with open(os.path.join(REPO, c["file"])) as f:
            model = json.load(f)
        for key, value in published.items():
            assert model[key] == value, (c["name"], key)
        assert model["num_hidden_layers"] < 32
        assert "llama" not in c["name"].lower()
