"""Engine layer: ``snapshot_share.ssm``'s statistic (``jit_snap`` device
time over the window: the wave-boundary copy of the whole wave state,
here the K/V pool beside the SSM state and conv windows), read by that
file's reader, in the hybrid cell. Moves ``serve_tok_s``."""

from pathlib import Path

from bench.harness import load_module

read = load_module(Path(__file__).with_name("snapshot_share.ssm.py")).read
