"""``BENCHMARK.json`` and the files it names, and the command's refusals:
no result without a TPU, none for a cell that ``BENCHMARK.json`` does
not list, and none from a checkout that holds only the benchmark."""
import json
import os
import pathlib
import re
import shutil
import subprocess
import sys


BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_top_level_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["paths"] == ["bench"]
    assert SPEC["command"] == ["python3", "bench/run.py"]
    assert 1 <= SPEC["run_seconds"] <= 51


def test_names_and_units():
    named = SPEC["configs"] + SPEC["workloads"] + SPEC["end_to_end"] + \
        SPEC["per_layer"]
    for entry in named:
        assert NAME.match(entry["name"]), entry["name"]
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
    names = [e["name"] for e in named]
    assert len(names) == len(set(names))


def test_every_cell_finds_its_files():
    configs = {c["name"]: c for c in SPEC["configs"]}
    for w in SPEC["workloads"]:
        cfg = configs[w["config"]]
        assert (ROOT / cfg["file"]).is_file()
        assert cfg["file"].startswith("bench/")
        traffic = json.loads((BENCH / "traffic" / f"{w['traffic']}.json")
                             .read_text())
        assert (BENCH / "traffic" / f"{traffic['kind']}.py").is_file()
        assert (BENCH / "limits" / f"{w['name']}.json").is_file()
        assert w["chips"] == 1
        e2e = [m["name"] for m in SPEC["end_to_end"]
               if w["name"] in m.get("workloads", [w["name"]])]
        assert "setup_s" in e2e and len(e2e) >= 2
        layer = [m for m in SPEC["per_layer"] if w["name"] in m["workloads"]]
        assert layer and all(m["moves"] in e2e for m in layer)


def test_every_per_layer_metric_has_a_reader():
    for m in SPEC["per_layer"]:
        assert (BENCH / "metrics" / f"{m['name']}.py").is_file(), m["name"]


def test_bounds():
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")


def _run(cwd, *extra, workload="sift128-ivf1024.fit"):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", workload,
         "--seed", "1", "--seconds", "1", *extra], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=300)


def test_no_result_without_a_tpu():
    out = _run(ROOT)
    assert out.returncode == 1
    assert out.stdout.strip() == ""
    assert "not 'tpu'" in out.stderr


def test_no_result_from_the_benchmark_alone(tmp_path):
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    out = _run(tmp_path, "--rehearse")
    assert out.returncode != 0
    assert not any(line.startswith("{") for line in out.stdout.splitlines())


def test_no_result_for_an_unlisted_cell():
    out = _run(ROOT, "--rehearse", workload="sift128-ivf1024.serve")
    assert out.returncode == 1
    assert out.stdout.strip() == ""
    assert "no workload 'sift128-ivf1024.serve'" in out.stderr
