"""A tiny cell for the benchmark's CPU tests, added the way a later change
would add one: new files and new entries in a copy of ``BENCHMARK.json``."""
import json
import shutil
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

TINY_CELL = "tiny.mix"
# Set from the tiny cell's CPU readings over seeds 1-8 and 11 (sound: loss
# <= 0.015, grad <= 0.18, grad_median <= 0.085, change <= 0.036; control:
# loss >= 0.41, grad >= 3.6, grad_median >= 0.52, change >= 1.01; half of
# the batch: grad_median >= 0.42).
TINY_LIMITS = {"loss": 0.05, "grad": 1.0, "grad_median": 0.2,
               "switch": 0, "change": 0.15}


def make_root(dest: Path, chips: int = 1) -> Path:
    """A checkout holding a copy of the benchmark plus the tiny cell."""
    shutil.copytree(ROOT / "bench", dest / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(ROOT / "BENCHMARK.json", dest / "BENCHMARK.json")
    cfg = json.loads((ROOT / "bench/configs/smollm-360m.json").read_text())
    cfg.update(name="tiny", hidden_size=64, intermediate_size=128,
               num_attention_heads=4, num_key_value_heads=2, head_dim=16,
               num_hidden_layers=2, vocab_size=256)
    # the XLA dispatch: a CPU test compiles it in seconds
    cfg["program"] = [o.replace("use_pallas=true", "use_pallas=false")
                      for o in cfg["program"]]
    (dest / "bench/configs/tiny.json").write_text(json.dumps(cfg))
    traffic = {"kind": "lm_train", "seq_len": 64, "global_batch": 8,
               "data_parallel": chips, "adapt_interval": 4, "log_every": 2,
               "noise": 0.05}
    (dest / "bench/traffic/tiny-mix.json").write_text(json.dumps(traffic))
    (dest / f"bench/limits/{TINY_CELL}.json").write_text(
        json.dumps({"limits": TINY_LIMITS}))
    bench = json.loads((dest / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tiny", "source": "test",
                             "file": "bench/configs/tiny.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": TINY_CELL, "config": "tiny",
                               "traffic": "tiny-mix", "chips": chips,
                               "why": "test"})
    (dest / "BENCHMARK.json").write_text(json.dumps(bench))
    return dest


@pytest.fixture
def no_compile_cache(monkeypatch):
    """Keep the persistent compile cache off in the test process."""
    from bench import run
    monkeypatch.setattr(run, "use_cache", lambda root: None)


def run_cell(root: Path, capsys, seed=11, step_wrapper=None, trace=0,
             switch_wrapper=None):
    """Drive a whole run of the tiny cell on the CPU; returns the result."""
    from bench import run
    rc = run.main(["--workload", TINY_CELL, "--seed", str(seed), "--seconds",
                   "1", "--trace", str(trace)], root=root,
                  require_accelerator=False, step_wrapper=step_wrapper,
                  switch_wrapper=switch_wrapper)
    out = capsys.readouterr()
    assert rc == 0, out.err[-2000:]
    return json.loads(out.out.strip().splitlines()[-1]), out.err
