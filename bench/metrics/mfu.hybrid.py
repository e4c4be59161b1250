"""The whole step against the bf16 peak in the hybrid cell:
``mfu.serve``'s statistic, read by that file's reader, with the
operations of the cell's model file (``granite_hybrid.token_flops`` /
``prefill_flops``). Moves ``serve_tok_s``."""

from pathlib import Path

from bench.harness import load_module

read = load_module(Path(__file__).with_name("mfu.serve.py")).read
