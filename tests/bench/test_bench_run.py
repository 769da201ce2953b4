"""bench/run.py on the CPU: it refuses to run off the chip and without
the simulator's sources, every cell runs end to end at a test size, and
a configuration, a traffic mix, an entry and a metric added as new
files are found without an edit."""
from __future__ import annotations

import json
import re
import shutil
import subprocess
import sys

import jax
import pytest
from bench_helpers import MESH_CELL, ROOT, dump, load, mini_root, run_cell

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
BENCH = load(ROOT / "BENCHMARK.json")
WORKLOADS = [w["name"] for w in BENCH["workloads"]]


@pytest.fixture(scope="module")
def small(tmp_path_factory):
    return mini_root(tmp_path_factory.mktemp("bench"))


def test_refuses_without_a_tpu(capsys):
    from bench import run

    assert jax.devices()[0].platform == "cpu"
    rc = run.main(["--workload", WORKLOADS[0], "--seed", "0",
                   "--seconds", "1"])
    assert rc != 0
    assert capsys.readouterr().out == ""


def test_refuses_without_the_simulator(tmp_path):
    """A checkout holding only BENCHMARK.json and the benchmark's own
    paths exits non-zero and prints no result."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for p in BENCH["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    proc = subprocess.run(
        [sys.executable, *BENCH["command"][1:], "--workload", WORKLOADS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, capture_output=True, text=True, timeout=120,
        env={"PATH": "/usr/bin:/bin", "JAX_PLATFORMS": "cpu"})
    assert proc.returncode != 0
    assert proc.stdout == ""


def test_benchmark_json_names_its_files():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    for cfg in BENCH["configs"]:
        assert NAME.match(cfg["name"])
        assert any(cfg["file"].startswith(p + "/") for p in BENCH["paths"])
        assert (ROOT / cfg["file"]).is_file()
        assert load(ROOT / cfg["file"])["reduced"] == cfg["reduced"]
    for wl in BENCH["workloads"]:
        assert NAME.match(wl["name"]) and wl["chips"] in (1, 4)
        assert (ROOT / "bench" / "traffic" / (wl["traffic"] + ".json")).is_file()
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25 and UNIT.match(m["unit"])
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e and UNIT.match(m["unit"])
        assert (ROOT / "bench" / "metrics" / (m["name"] + ".py")).is_file()
        assert set(m.get("workloads", WORKLOADS)) <= set(WORKLOADS)


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("workload", WORKLOADS + [MESH_CELL["name"]])
def test_cell_runs_and_checks_on_cpu(small, workload, trace):
    rc, result, err = run_cell(small, workload, trace=trace)
    assert rc == 0, err
    assert result["correct"] is True, err
    assert result["failed"] == 0 and result["attempted"] >= 1
    assert list(result)[-1] == "checks"
    assert result["device"]["platform"] == "cpu"
    assert err.strip().splitlines()[-1].startswith("check ")
    if trace:
        # no TPU trace on the CPU: the device readers find nothing
        assert "device_idle_share" not in result["metrics"]
        assert "setup_compile_s" in result["metrics"]
    else:
        assert set(result["metrics"]) == {"sim_bursts_per_s", "setup_s"}
        assert result["metrics"]["sim_bursts_per_s"]["value"] > 0


ENTRY = """
import jax.numpy as jnp

from bench.generator import verdict


class Cell:
    def __init__(self, config, traffic, seed, devices):
        self.bursts_per_call = traffic["n"]

    def call(self):
        return int(jnp.arange(self.bursts_per_call).sum())

    def check(self, outs, control=False):
        n = self.bursts_per_call
        return verdict({"sum_off": (abs(outs[-1] - n * (n - 1) // 2), 0)},
                       [str(o) for o in outs])

    def notes(self, out):
        return []

    def close(self):
        pass
"""


def test_new_config_mix_entry_and_metric_are_found_by_name(tmp_path):
    root = mini_root(tmp_path)
    cfg = load(root / "bench/configs/nvdla-soc-yolov3.json")
    cfg.update(name="soc-tiny", window_bursts=256)
    dump(cfg, root / "bench/configs/soc-tiny.json")
    dump({"entry": "campaign", "name": "two", "mixes": [[0, "l1"], [1, "llc"]],
          "batch_points": 2, "mesh": False},
         root / "bench/traffic/two-mixes.json")
    dump({"entry": "arange", "n": 1000}, root / "bench/traffic/arange.json")
    (root / "bench/entries/arange.py").write_text(ENTRY)
    (root / "bench/metrics/calls_seen.py").write_text(
        "def read(run):\n    return run.calls\n")
    bench = load(root / "BENCHMARK.json")
    bench["configs"].append({**bench["configs"][0], "name": "soc-tiny",
                             "file": "bench/configs/soc-tiny.json"})
    for name, traffic in (("soc-tiny.two-mixes", "two-mixes"),
                          ("soc-tiny.arange", "arange")):
        bench["workloads"].append({"name": name, "config": "soc-tiny",
                                   "traffic": traffic, "chips": 1,
                                   "why": "test"})
    bench["per_layer"].append({"name": "calls_seen", "unit": "calls",
                               "better": "higher", "source": "host_clock",
                               "layer": "test", "moves": "sim_bursts_per_s",
                               "workloads": ["soc-tiny.two-mixes",
                                             "soc-tiny.arange"]})
    dump(bench, root / "BENCHMARK.json")
    rc, result, err = run_cell(root, "soc-tiny.two-mixes", trace=1)
    assert rc == 0 and result["correct"], err
    assert result["metrics"]["calls_seen"]["value"] == result["attempted"]
    assert json.loads(err.splitlines()[0])["bursts_per_call"] == 256 * 3
    rc, result, err = run_cell(root, "soc-tiny.arange", trace=1)
    assert rc == 0 and result["correct"], err
    assert result["checks"]["sum_off"] == {"value": 0, "limit": 0}
