"""A checkout-like root holding a copy of bench/ and a tiny cell, for runs
of the whole harness on the CPU."""
from __future__ import annotations

import json
import shutil
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
CELL = "tiny.mix"

# the served path's widest gap on this config, CPU, `ref` kernel: about
# 2e-3 (bf16 activations against the float32 reference); see test_faults
LIMIT = 0.02


def tiny_spec(**over) -> dict:
    with open(ROOT / "bench" / "configs" / "qwen3-1.7b.json") as f:
        spec = json.load(f)
    spec.update(name="tiny", hidden_size=256, intermediate_size=512,
                num_hidden_layers=2, num_attention_heads=4,
                num_key_value_heads=2, head_dim=64, vocab_size=512)
    spec["check"] = {"max_logit_gap": LIMIT}
    spec.update(over)
    return spec


# short prompts and longer answers, so a request's context is mostly its
# own decoded tokens by the end of a few seconds' window
TINY_MIX = {"loop": "closed", "clients": 4, "max_total": 1024,
            "pairs": [[8, 30], [16, 24], [10, 28], [12, 20]]}


def make_root(tmp: Path, spec: dict = None, mix: dict = None) -> Path:
    """tmp/BENCHMARK.json with the one cell `tiny.mix`, and tmp/bench a
    copy of bench/ plus the tiny configuration and mix."""
    shutil.copytree(ROOT / "bench", tmp / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    with open(tmp / "bench" / "configs" / "tiny.json", "w") as f:
        json.dump(spec or tiny_spec(), f)
    with open(tmp / "bench" / "traffic" / "tiny.json", "w") as f:
        json.dump(mix or TINY_MIX, f)
    with open(ROOT / "BENCHMARK.json") as f:
        b = json.load(f)
    b["configs"] = [{"name": "tiny", "source": "test", "reduced": [],
                     "file": "bench/configs/tiny.json", "why": "test"}]
    b["workloads"] = [{"name": CELL, "config": "tiny", "traffic": "tiny",
                       "chips": 1, "why": "test"}]
    for m in b["per_layer"]:
        m["workloads"] = [CELL]
    with open(tmp / "BENCHMARK.json", "w") as f:
        json.dump(b, f)
    return tmp


def run(root: Path, seconds: float = 6.0, trace: bool = False,
        seed: int = 2**31 + 7, cell: str = CELL) -> dict:
    """The whole run on the CPU: the harness's look for a chip skipped,
    the jnp paged-attention kernel, v5e roofline constants."""
    from bench.run import run_cell
    from repro.config import TPU_V5E
    return run_cell(root, cell, seed, seconds, trace,
                    t_start=time.perf_counter(), require_chip=False,
                    backend="ref", hw=TPU_V5E)
