"""Model-step layer: ``prefill_share.serve``'s statistic (``jit_pf`` +
``jit_admit`` device time over the window: the chunked SSD prefill and
the handoff of its state and conv window into the slot), read by that
file's reader, in the SSM cell. Moves ``serve_tok_s``."""

from pathlib import Path

from bench.harness import load_module

read = load_module(Path(__file__).with_name("prefill_share.serve.py")).read
