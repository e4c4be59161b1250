"""A checkout-shaped copy of the benchmark with tiny cells added as data
alone: the same harness, models and metric readers, small widths, so a
whole run fits on the CPU in a test."""

from __future__ import annotations

import json
import shutil
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]

TINY_SPECS = {
    "tiny-dense": {
        "name": "tiny-dense", "architecture": "dense_gqa",
        "program_config": "granite_3_2b", "dtype": "float32",
        "hidden_size": 64, "num_hidden_layers": 2,
        "num_attention_heads": 4, "num_key_value_heads": 2,
        "head_dim": 16, "intermediate_size": 128, "vocab_size": 256,
        "rope_theta": 10000.0, "tie_word_embeddings": True,
        "rms_norm_eps": 1e-06},
    "tiny-ssd": {
        "name": "tiny-ssd", "architecture": "mamba2_ssd",
        "program_config": "mamba2_1p3b", "dtype": "float32",
        "d_model": 64, "n_layer": 2, "vocab_size": 256,
        "tie_embeddings": True, "rms_norm_eps": 1e-06,
        "mamba2_layer": {"d_state": 16, "expand": 2, "headdim": 16}},
}

TINY_MIX = {
    "engine": {"slots": 4, "max_ctx": 128, "page_size": 16, "wave_len": 4},
    "requests": 3000, "block": 4,
    "prompt_len": {"values": [16, 32], "weights": [0.5, 0.5]},
    "output_len": {"dist": "uniform", "low": 8, "high": 24},
    "check": {"requests": 3},
}


def tiny_root(tmp: Path) -> Path:
    """Copy the checkout's benchmark into ``tmp`` and add one tiny cell
    per architecture by data files and BENCHMARK.json entries only; each
    is held to the granite cell's limit."""
    limit = json.loads((ROOT / "bench" / "cells" /
                        "granite-3-2b.longctx.json").read_text())["logit_gap"]
    root = tmp / "checkout"
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    (root / "bench" / "traffic" / "tiny.json").write_text(
        json.dumps(TINY_MIX))
    for name, spec in TINY_SPECS.items():
        f = f"bench/configs/{name}.json"
        (root / f).write_text(json.dumps(spec))
        bench["configs"].append({"name": name, "source": "test", "file": f,
                                 "reduced": [], "why": "test"})
        cell = f"{name}.tiny"
        bench["workloads"].append({"name": cell, "config": name,
                                   "traffic": "tiny", "chips": 1,
                                   "why": "test"})
        (root / "bench" / "cells" / f"{cell}.json").write_text(
            json.dumps({"logit_gap": limit}))
        for m in bench["per_layer"]:
            m.setdefault("workloads", []).append(cell)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root
