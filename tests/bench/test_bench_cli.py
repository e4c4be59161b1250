"""The command refuses to run without a TPU, and never falls back."""

import os
import subprocess
import sys

from bench_tiny import ROOT


def test_no_tpu_exits_nonzero_with_a_message():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "run.py"), "--workload",
         "granite-3-2b.longctx", "--seed", "3", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True,
        text=True, timeout=300)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert p.stdout.strip() == ""


def test_unknown_device_kind_is_refused(tmp_path, monkeypatch):
    import jax
    import pytest

    from bench import harness

    class Dev:
        platform, device_kind = "tpu", "TPU v99"

    monkeypatch.setattr(jax, "devices", lambda *a: [Dev()])
    with pytest.raises(harness.NoChip, match="no entry"):
        harness.chip(ROOT, 1)
    with pytest.raises(harness.NoChip, match="needs 4 chips"):
        harness.chip(ROOT, 4)
