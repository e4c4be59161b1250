"""The harness finds every configuration, mix, cell and metric file by
name, ``BENCHMARK.json`` keeps to the benchmark's shape, and a cell
added as data alone is picked up."""

import json
import re

import pytest

from bench import harness
from bench_tiny import ROOT, tiny_root

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in BENCH["workloads"]]


def test_benchmark_json_keys_and_names():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["file"].startswith("bench/")
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and w["chips"] in (1, 4)
        assert len(w["why"]) <= 200
    names = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(names) == len(set(names))
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    assert e2e == {"serve_tok_s", "setup_s"}
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in BENCH["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["moves"] in e2e and set(m["workloads"]) <= set(CELLS)


@pytest.mark.parametrize("name", CELLS)
def test_every_cell_finds_its_files(name):
    cell = harness.load_cell(ROOT, name)
    assert cell.spec["name"] == cell.entry["config"]
    assert set(cell.readers) == {m["name"] for m in BENCH["per_layer"]
                                 if name in m["workloads"]}
    assert all(callable(r) for r in cell.readers.values())
    assert cell.limits["logit_gap"] > 0
    assert set(cell.end_to_end) == {"serve_tok_s", "setup_s"}
    for f in ("init_params", "hidden", "head", "decode_bytes",
              "token_flops", "prefill_flops", "program_fields"):
        assert callable(getattr(cell.model, f))


def test_a_cell_added_as_data_alone_is_picked_up(tmp_path):
    root = tiny_root(tmp_path)
    before = {p.relative_to(root) for p in (root / "bench").rglob("*.py")}
    assert before == {p.relative_to(ROOT) for p in
                      (ROOT / "bench").rglob("*.py")
                      if "__pycache__" not in p.parts}
    for name in ("tiny-dense.tiny", "tiny-ssd.tiny"):
        cell = harness.load_cell(root, name)
        assert cell.mix["engine"]["slots"] == 4
        assert set(cell.readers) == {m["name"] for m in BENCH["per_layer"]}
    with pytest.raises(KeyError):
        harness.load_cell(root, "no-such.cell")
