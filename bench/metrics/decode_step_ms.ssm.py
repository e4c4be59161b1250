"""Model-step layer: ``decode_step_ms.serve``'s statistic (device time
of ``jit_wave`` over the window's decode steps), read by that file's
reader, in the SSM cell. Moves ``serve_tok_s``."""

from pathlib import Path

from bench.harness import load_module

read = load_module(Path(__file__).with_name("decode_step_ms.serve.py")).read
