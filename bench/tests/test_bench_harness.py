"""The harness is driven by data: a cell, configuration, traffic mix or
per-layer metric added as a file runs with no existing file edited; bad
names and units are refused at load; without a chip or without the
system under test a run exits non-zero and prints no result."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from bench import harness
from bench.tests.tiny_cells import tiny

ROOT = Path(__file__).resolve().parents[2]


def _copy_checkout(tmp: Path) -> Path:
    """BENCHMARK.json and the files under ``bench``, nothing else."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", tmp / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    return tmp


def _load_harness(root: Path):
    return harness.load_module(root / "bench" / "harness.py",
                               f"bench_harness_{root.name}")


def test_added_files_run_without_editing_any(tmp_path):
    root = _copy_checkout(tmp_path)
    os.symlink(ROOT / "src", root / "src")
    bench = root / "bench"
    conf = json.loads((bench / "configs" / "ctgan-adult.json").read_text())
    conf["name"] = "ctgan-adult-half"
    conf["rows"] = 20_000
    (bench / "configs" / "ctgan-adult-half.json").write_text(
        json.dumps(conf))
    mix = json.loads((bench / "traffic" /
                      "fed-quantity-skew-p5.json").read_text())
    mix["clients"] = 4
    (bench / "traffic" / "fed-quantity-skew-p4.json").write_text(
        json.dumps(mix))
    shutil.copy(bench / "workloads" / "adult-train-p5.json",
                bench / "workloads" / "half-train-p4.json")
    (bench / "metrics" / "rows_per_dispatch.py").write_text(
        "def read(ctx):\n    return float(ctx['cell'].rows_per_dispatch)\n")
    bm = json.loads((root / "BENCHMARK.json").read_text())
    bm["configs"].append({"name": "ctgan-adult-half", "source": "x",
                          "file": "bench/configs/ctgan-adult-half.json",
                          "reduced": ["rows"], "why": "test"})
    bm["workloads"].append({"name": "half-train-p4",
                            "config": "ctgan-adult-half",
                            "traffic": "fed-quantity-skew-p4", "chips": 1,
                            "why": "test"})
    for m in bm["end_to_end"]:
        if "train_rows_per_s" == m["name"]:
            m["workloads"].append("half-train-p4")
    bm["per_layer"].append({"name": "rows_per_dispatch", "unit": "rows",
                            "better": "higher", "source": "program_counter",
                            "layer": "federation", "moves":
                            "train_rows_per_s", "workloads":
                            ["half-train-p4"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bm))

    h = _load_harness(root)
    bm = h.load_benchmark(root)
    spec = h.cell_spec(bm, "half-train-p4")
    assert spec["traffic_mix"]["clients"] == 4
    assert spec["model"]["rows"] == 20_000

    def shrink(spec):
        tiny(spec)
        spec["traffic_mix"]["clients"] = 4
    line = h.run("half-train-p4", 2, 0.3, False, t_start=0.0,
                 require_chip=False, bm=bm, patch_spec=shrink)
    assert line["correct"], line["limits"]
    assert set(line["metrics"]) == {"train_rows_per_s", "setup_s"}
    [layer] = [m["name"] for m in h.metrics_of(bm, "half-train-p4",
                                                "per_layer")]
    cell = type("C", (), {"rows_per_dispatch": 1000})()
    assert h.reader(layer).read({"cell": cell}) == 1000.0


@pytest.mark.parametrize("field,value", [
    ("name", "adult train"), ("name", "a,b"), ("name", "x/y"),
    ("name", "-lead"), ("unit", "rows per s"), ("unit", "µs"),
    ("unit", "a" * 17)])
def test_bad_names_and_units_are_refused(tmp_path, field, value):
    root = _copy_checkout(tmp_path)
    bm = json.loads((root / "BENCHMARK.json").read_text())
    bm["end_to_end"][0][field] = value
    (root / "BENCHMARK.json").write_text(json.dumps(bm))
    with pytest.raises(harness.BenchError):
        harness.load_benchmark(root)


def _run(cwd: Path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "adult-train-p5",
         "--seed", "3000000001", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_chip_exits_nonzero_without_a_result():
    out = _run(ROOT)
    assert out.returncode != 0 and out.stdout.strip() == ""
    assert "TPU" in out.stderr


def test_without_the_system_exits_nonzero(tmp_path):
    out = _run(_copy_checkout(tmp_path))
    assert out.returncode != 0 and out.stdout.strip() == ""


def test_kernel_fallbacks_count_ref_and_interpreted_routes(monkeypatch):
    import collections
    from repro.kernels import ops
    monkeypatch.setattr(ops, "DISPATCH_COUNTS", collections.Counter(
        {"weighted_agg": 3, "segment_activations": 2}))
    monkeypatch.setattr(ops, "INTERPRET_COUNTS", collections.Counter())
    assert harness.kernel_fallbacks() == 0
    ops.DISPATCH_COUNTS["vgm_decode_table_ref"] += 1
    ops.INTERPRET_COUNTS["weighted_agg"] += 2
    assert harness.kernel_fallbacks() == 3
