"""A whole run of a tiny cell on the CPU, past the harness's look for a
chip: sound, it is correct; with the timed path broken underneath, or
with the fp8 control in the program's place, ``correct`` comes out
false. One chip serves a cell, so there is no exchange between chips to
leave out."""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness
from bench_tiny import tiny_root

CELLS = ["tiny-dense.tiny", "tiny-ssd.tiny"]
SEED = 2**34 + 5


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_root(tmp_path_factory.mktemp("faults"))


@pytest.fixture(autouse=True)
def fresh_programs():
    """Each run builds its executables anew (a fault must be traced in),
    and the persistent cache a run turns on is turned off after it."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    from repro.core.schedule import EXEC_CACHE

    EXEC_CACHE.clear()
    yield
    EXEC_CACHE.clear()
    jax.config.update("jax_compilation_cache_dir", None)
    cc.reset_cache()


def run(root, name, monkeypatch):
    """A whole run, with the harness's look for a chip answered by the
    CPU and the chip's peaks."""
    peaks = harness.load_json(root / "bench" / "peaks.json")["devices"][
        "TPU v5 lite"]
    monkeypatch.setattr(harness, "chip", lambda root, chips: (
        {"platform": "cpu", "kind": "cpu", "count": 1}, peaks))
    return harness.run(root, name, SEED, 0.5, False,
                       t_start=time.monotonic())


def state_unchanged(mp):
    """The decode step hands back the cache it was given."""
    from repro.models import lm

    step = lm.decode_step
    mp.setattr(lm, "decode_step",
               lambda cfg, p, cache, t, i: (step(cfg, p, cache, t, i)[0],
                                            cache))


def half_batch(mp):
    """The second half of the slots get the first half's logits."""
    from repro.models import lm

    step = lm.decode_step

    def broken(cfg, p, cache, t, i):
        lg, c = step(cfg, p, cache, t, i)
        h = lg.shape[0] // 2
        return jnp.concatenate([lg[:lg.shape[0] - h], lg[:h]]), c

    mp.setattr(lm, "decode_step", broken)


def token_altered(mp):
    """One served token of each finished request is changed as the
    wave's results are committed."""
    from repro.runtime.serve import DecodeEngine

    commit = DecodeEngine.commit_wave

    def broken(self):
        fin, toks, steps = commit(self)
        for _, _, res in fin:
            if res.emitted:
                k = res.prompt_len + res.emitted // 2
                res.tokens[k] = (res.tokens[k] + 1) % self.cfg.vocab
        return fin, toks, steps

    mp.setattr(DecodeEngine, "commit_wave", broken)


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(root, name, monkeypatch):
    line = run(root, name, monkeypatch)
    assert line["correct"], line["checks"]
    assert line["checks"]["served_tokens_compared"]["value"] > 20
    assert list(line)[-1] == "checks"
    assert set(line["metrics"]) == {"serve_tok_s", "setup_s"}
    assert line["failed"] == 0 and line["attempted"] >= 4


@pytest.mark.parametrize("fault", [state_unchanged, half_batch,
                                   token_altered])
@pytest.mark.parametrize("name", CELLS)
def test_broken_path_is_not_correct(root, name, fault, monkeypatch):
    fault(monkeypatch)
    line = run(root, name, monkeypatch)
    assert not line["correct"]
    assert line["checks"]["logit_gap"]["value"] > \
        line["checks"]["logit_gap"]["limit"]


@pytest.mark.parametrize("name", CELLS)
def test_fp8_control_fails_the_limit(root, name):
    cell = harness.load_cell(root, name)
    peaks = harness.load_json(root / "bench" / "peaks.json")["devices"][
        "TPU v5 lite"]
    win, params = harness.serve_window(cell, SEED, 0.5, peaks=peaks,
                                       trace=False)
    picked = harness.sample(win.results, 3, SEED)
    gaps = harness.served_gaps(cell, params, picked, control=True)
    assert gaps["program"] <= cell.limits["logit_gap"] < gaps["control"]
    assert np.isfinite(gaps["control"])
