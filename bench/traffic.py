"""The one traffic generator: it reads a mix's parameters from
``bench/traffic/<mix>.json`` and draws the cell's inputs from the seed.

``kind: decode`` — a fixed batch of prompts of one length, prefilled in
set-up and then decoded greedily back to back.  The seed draws the prompt
tokens; the sizes are the mix's, the same for every seed.
"""
from __future__ import annotations

import json
import pathlib

import numpy as np

MIX_DIR = pathlib.Path(__file__).resolve().parent / "traffic"


def load_mix(name: str) -> dict:
    return json.loads((MIX_DIR / f"{name}.json").read_text())


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), stream])


def decode_prompts(mix: dict, seed: int, vocab: int) -> np.ndarray:
    """``[batch, prompt_len]`` int32 prompt tokens."""
    return _rng(seed, 0).integers(
        0, vocab, (mix["batch"], mix["prompt_len"])).astype(np.int32)
