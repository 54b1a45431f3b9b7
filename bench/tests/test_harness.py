"""``BENCHMARK.json`` against the benchmark's contract, the harness finding
every file of a cell by name, and the refusals without a chip."""
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from bench.lib import harness
from bench.lib.peaks import UnknownDevice, peaks_for

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)
CELLS = [w["name"] for w in BENCH["workloads"]]


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "bench/run.py"]
    assert BENCH["paths"] == ["bench"]
    assert 1 <= BENCH["run_seconds"] <= 51
    assert len(json.dumps(BENCH)) < 64 * 1024


def test_entries_and_names():
    names = set()
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("bench/configs/")
        assert os.path.exists(os.path.join(harness.ROOT, c["file"]))
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for group in (BENCH["configs"], BENCH["workloads"],
                  BENCH["end_to_end"] + BENCH["per_layer"]):
        assert len({x["name"] for x in group}) == len(group)
        names |= {x["name"] for x in group}
    assert all(NAME.match(n) for n in names)
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) <= max(
        1, len(BENCH["workloads"]) // 2)


def test_every_cell_reports_what_its_layer_metrics_move():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25 and "workloads" not in e2e[
        "setup_s"]
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in BENCH["per_layer"]:
        moved = e2e[m["moves"]]
        for cell in m["workloads"]:
            assert cell in CELLS
            assert "workloads" not in moved or cell in moved["workloads"]
    for cell in CELLS:
        c = harness.load_cell(cell)
        assert len(c.end_to_end) >= 2 and c.per_layer


@pytest.mark.parametrize("cell", CELLS)
def test_finds_every_file_of_a_cell_by_name(cell):
    c = harness.load_cell(cell)
    assert harness.driver(c).run
    assert c.model.linears(c.config)
    assert c.workload["limits"]
    for m in c.per_layer:
        assert harness.metric_reader(m["name"]).read


def test_a_new_cell_needs_only_new_files(tmp_path):
    """A cell added by a new entry and new files, with no file edited."""
    bench = json.loads(json.dumps(BENCH))
    bench["workloads"].append({"name": "danube.engine.small", "config":
                               "h2o-danube-3-4b", "traffic": "small",
                               "chips": 1, "why": "test"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    shutil.copytree(os.path.join(harness.BENCH, "traffic"),
                    tmp_path / "traffic")
    shutil.copytree(os.path.join(harness.BENCH, "workloads"),
                    tmp_path / "workloads")
    (tmp_path / "traffic" / "small.json").write_text(json.dumps(
        {"rows": 512, "dtype": "bfloat16", "out_dtype": "bfloat16"}))
    shutil.copy(tmp_path / "workloads" / "danube.engine.prefill4k.json",
                tmp_path / "workloads" / "danube.engine.small.json")
    c = harness.load_cell("danube.engine.small",
                          bench_file=str(tmp_path / "BENCHMARK.json"),
                          bench_dir=str(tmp_path))
    assert c.workload["rows"] == 512 and c.workload["driver"] == "engine"
    with pytest.raises(KeyError):
        harness.load_cell("no.such.cell")


def test_unknown_device_kind_is_an_error():
    assert peaks_for("TPU v5 lite").bf16_flops == 197e12
    with pytest.raises(UnknownDevice):
        peaks_for("TPU v99")


def _run(root, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, os.path.join(root, "bench", "run.py"), "--workload",
         CELLS[0], "--seed", str(2**33 + 1), "--seconds", "1", *extra],
        cwd=root, env=env, capture_output=True, text=True, timeout=300)


def test_refuses_without_a_tpu():
    done = _run(harness.ROOT)
    assert done.returncode != 0 and done.stdout == ""
    assert "no TPU" in done.stderr


def test_the_benchmark_alone_does_not_run(tmp_path):
    shutil.copy(os.path.join(harness.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(harness.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    done = _run(str(tmp_path))
    assert done.returncode != 0 and done.stdout == ""
    assert "No module named 'repro'" in done.stderr
