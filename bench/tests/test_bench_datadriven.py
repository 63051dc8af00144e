"""The harness is driven by data: a configuration, a traffic mix, a
per-layer metric and a cell added as new files and new entries are found by
name, with no file that was there edited."""
import hashlib
import json
import os
import subprocess
import sys
from types import SimpleNamespace

from conftest import ROOT, TINY_CELL, make_root, run_cell

PROBE = '''"""probe.window_steps: the steps in the traced window."""


def read(ctx):
    return float(ctx.steps) if ctx.steps else None
'''


def _digests(root):
    out = {}
    for dirpath, _, files in os.walk(root):
        for f in files:
            p = os.path.join(dirpath, f)
            out[os.path.relpath(p, root)] = hashlib.sha256(
                open(p, "rb").read()).hexdigest()
    return out


def test_new_files_are_found_by_name(tmp_path, capsys, no_compile_cache):
    before = _digests(ROOT / "bench")
    root = make_root(tmp_path)
    (root / "bench/metrics/probe.window_steps.py").write_text(PROBE)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["per_layer"].append({
        "name": "probe.window_steps", "unit": "steps", "better": "higher",
        "source": "program_counter", "layer": "train step",
        "moves": "train_tokens_per_s", "workloads": [TINY_CELL]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    # the files the copy started from are byte for byte the repository's
    copied = _digests(root / "bench")
    assert all(copied[k] == v for k, v in before.items()
               if not k.startswith(("tests", "__pycache__"))
               and "__pycache__" not in k)

    result, _ = run_cell(root, capsys)
    assert result["correct"] is True, result["checks"]
    assert set(result["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert result["metrics"]["train_tokens_per_s"]["value"] > 0
    assert result["device"]["count"] == 1

    # the cell's per-layer metrics are looked up by name: the new one is
    # read, those listed only for other cells are not
    from bench import run
    ctx = SimpleNamespace(steps=10)
    got = run.per_layer(root, bench, TINY_CELL, ctx)
    assert got == {"probe.window_steps": {"value": 10.0, "unit": "steps"}}


def _run_cli(cwd, extra_env=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(extra_env or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "smollm-360m.train-s2k", "--seed", "3000000000", "--seconds", "1"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_runner_refuses_a_host_without_a_tpu():
    proc = _run_cli(ROOT)
    assert proc.returncode != 0
    assert proc.stdout == ""
    assert "TPU" in proc.stderr


def test_runner_refuses_a_checkout_without_the_program(tmp_path):
    """A directory that holds only BENCHMARK.json and the benchmark."""
    import shutil
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    proc = _run_cli(tmp_path, {"PYTHONPATH": ""})
    assert proc.returncode != 0
    assert proc.stdout == ""


def test_every_cell_limits_every_number():
    """Each cell's limits file gives a limit for every number compared: a
    number without one would read not correct on every run."""
    from bench import check
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        limits = check.load_limits(str(ROOT), w["name"])
        assert limits is not None and set(limits) == set(check.NUMBERS), \
            (w["name"], limits)
