"""`BENCHMARK.json` is well formed, and the harness finds a cell's files by
name, so that a new configuration, mix, loop kind or metric is added
without editing an existing file."""
import importlib.util
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

import _paths

SPEC = json.load(open(os.path.join(_paths.ROOT, "BENCHMARK.json")))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
E2E = {m["name"]: m for m in SPEC["end_to_end"]}
CELLS = {w["name"]: w for w in SPEC["workloads"]}


def test_top_level_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["command"][1].startswith(SPEC["paths"][0] + "/")
    assert 1 <= SPEC["run_seconds"] <= 51


def test_names_units_and_text():
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in SPEC[k]]
    assert all(NAME.match(n) for n in names), names
    for k in ("end_to_end", "per_layer"):
        assert len({m["name"] for m in SPEC[k]}) == len(SPEC[k])
        for m in SPEC[k]:
            assert UNIT.match(m["unit"]), m
            assert m["better"] in ("lower", "higher")
    for w in SPEC["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
        assert w["chips"] in (1, 4)
    assert sum(w["chips"] == 4 for w in SPEC["workloads"]) <= max(
        1, len(SPEC["workloads"]) // 2)


def test_bounds_and_setup():
    assert "setup_s" in E2E
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")


def test_every_config_has_a_cell_and_its_files():
    used = {w["config"] for w in SPEC["workloads"]}
    for c in SPEC["configs"]:
        assert c["name"] in used
        assert c["file"].startswith(SPEC["paths"][0] + "/")
        assert os.path.exists(os.path.join(_paths.ROOT, c["file"]))
    for w in SPEC["workloads"]:
        for sub, name in (("traffic", w["traffic"]), ("limits", w["name"])):
            assert os.path.exists(os.path.join(_paths.BENCH, sub,
                                               name + ".json")), (sub, name)
        mix = json.load(open(os.path.join(_paths.BENCH, "traffic",
                                          w["traffic"] + ".json")))
        assert os.path.exists(os.path.join(_paths.BENCH, "loops",
                                           mix["loop"] + ".py")), mix


@pytest.mark.parametrize("metric", SPEC["per_layer"],
                         ids=[m["name"] for m in SPEC["per_layer"]])
def test_per_layer_metric_moves_a_reported_metric(metric):
    assert metric["moves"] in E2E
    moved = E2E[metric["moves"]]
    for cell in metric["workloads"]:
        assert cell in CELLS
        assert cell in moved.get("workloads", [cell])
    assert os.path.exists(os.path.join(_paths.BENCH, "metrics",
                                       metric["name"] + ".py"))


def test_every_cell_reports_setup_another_e2e_and_a_layer():
    for cell in CELLS:
        e2e = [m for m in SPEC["end_to_end"]
               if cell in m.get("workloads", [cell])]
        assert "setup_s" in {m["name"] for m in e2e}
        assert len(e2e) >= 2
        assert any(cell in m["workloads"] for m in SPEC["per_layer"])


def test_new_mix_and_metric_found_by_name(tmp_path):
    """A copy of the benchmark gains a mix, a metric and a cell by adding
    files and entries only; the harness finds all three."""
    shutil.copytree(_paths.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".jax_cache", ".traces",
                                                  "__pycache__"))
    spec = json.loads(json.dumps(SPEC))
    (tmp_path / "bench/traffic/study-small.json").write_text(json.dumps(
        dict(json.load(open(tmp_path / "bench/traffic/study.json")),
             check_units=4)))
    (tmp_path / "bench/metrics/lanes.study.py").write_text(
        "def read(run):\n    return run['lane_events']\n")
    (tmp_path / "bench/limits/homog-small.json").write_text(
        (tmp_path / "bench/limits/homog-study.json").read_text())
    spec["workloads"].append({"name": "homog-small", "config": "paper-homog",
                              "traffic": "study-small", "chips": 1,
                              "why": "a copy"})
    spec["end_to_end"][0]["workloads"].append("homog-small")
    spec["per_layer"].append({"name": "lanes.study", "unit": "events",
                              "better": "higher", "source": "device_trace",
                              "layer": "device event loop",
                              "moves": "experiments_per_s",
                              "workloads": ["homog-small"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    mod_spec = importlib.util.spec_from_file_location(
        "run_cell_copy", tmp_path / "bench/run_cell.py")
    run_cell = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(run_cell)
    _, cell, cfg, mix, limits = run_cell.load_cell("homog-small")
    assert mix["check_units"] == 4 and cfg["flows"]["nodes"] == 100
    assert limits == json.load(open(_paths.BENCH + "/limits/homog-study.json"))
    wanted = [m["name"] for m in run_cell.cell_metrics(spec, "homog-small",
                                                       True)]
    assert wanted == ["lanes.study"]
    assert run_cell.load_reader("lanes.study")({"lane_events": 7}) == 7
    assert [m["name"] for m in run_cell.cell_metrics(
        spec, "homog-small", False)] == ["experiments_per_s", "setup_s"]


ONE_FLOW = """\
from study import StudyLoop


class OneFlow(StudyLoop):
    \"\"\"Studies of the configuration's first flow only.\"\"\"

    def flows(self, index):
        name, flow = next(iter(super().flows(index).items()))
        return {name: flow}


LOOP = OneFlow
"""


def test_new_loop_kind_runs_by_name(tmp_path):
    """A copy of the benchmark gains a loop kind (a module that builds on
    the shared study loop), a mix that names it and a cell, by adding files
    and entries only; a whole CPU run of the new cell comes out correct."""
    shutil.copytree(_paths.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".jax_cache", ".traces",
                                                  "__pycache__"))
    spec = json.loads(json.dumps(SPEC))
    (tmp_path / "bench/loops/one-flow.py").write_text(ONE_FLOW)
    (tmp_path / "bench/traffic/one-flow.json").write_text(json.dumps(
        {"loop": "one-flow", "why": "studies of one flow",
         "check_units": 8, "trace_units": 1}))
    (tmp_path / "bench/limits/homog-one.json").write_text(
        (tmp_path / "bench/limits/homog-study.json").read_text())
    spec["workloads"].append({"name": "homog-one", "config": "paper-homog",
                              "traffic": "one-flow", "chips": 1,
                              "why": "one flow"})
    spec["end_to_end"][0]["workloads"].append("homog-one")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(_paths.ROOT, "src"))
    proc = subprocess.run(
        [sys.executable, str(tmp_path / "bench/run_cell.py"), "--workload",
         "homog-one", "--seed", str(2 ** 31 + 3), "--seconds", "0.1",
         "--allow-cpu", "--override", "config.flows.n_jobs=300",
         "--override", "config.scale_ratios=[1, 10, 100]",
         "--override", "config.init_props=[0.1, 0.3]"],
        env=env, capture_output=True, text=True, timeout=600,
        cwd=str(tmp_path))
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["correct"] is True, out
    assert out["attempted"] % 6 == 0 and out["attempted"] > 0, out
    assert set(out["metrics"]) == {"experiments_per_s", "setup_s"}
