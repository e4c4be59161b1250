"""Model-step layer: ``prefill_share.serve``'s statistic (``jit_pf`` +
``jit_admit`` device time over the window: 2-3.5k-token prefills through
chunked-SSD and attention layers, and their handoff into the slot),
read by that file's reader, in the hybrid cell. Moves
``serve_tok_s``."""

from pathlib import Path

from bench.harness import load_module

read = load_module(Path(__file__).with_name("prefill_share.serve.py")).read
