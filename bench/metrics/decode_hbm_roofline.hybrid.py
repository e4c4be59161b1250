"""Model-step layer, the hybrid decode step against the HBM peak:
``decode_hbm_roofline.serve``'s statistic, read by that file's reader,
with the bytes of the cell's model file (``granite_hybrid.decode_bytes``:
the weights once a step; each live row's f32 state and conv windows read
and written, and its valid keys and values read, once a token) over
``jit_wave`` time x peak. Moves ``serve_tok_s``."""

from pathlib import Path

from bench.harness import load_module

read = load_module(
    Path(__file__).with_name("decode_hbm_roofline.serve.py")).read
