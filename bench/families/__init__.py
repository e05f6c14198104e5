"""What the benchmark knows of each model family apart from its
equations: ``bench/families/<family>.py``, found by a configuration's
``model.family``.  A family module defines

* ``draw(m, keys)``: the weight tree of the configuration's ``model``
  object ``m``, drawn as `bench.weights.normal` draws (a fixed magnitude
  times a sign from the seed), in the layout the repo's models read;
* ``projections(m)``: ``name -> (n_in, n_out, copies per forward)`` of
  every weight matrix (a block that a forward calls 6 times has 6 copies);
* ``attention_flops(m)``: attention FLOPs per token per context position;
* ``SCOPES``: the program's ``jax.named_scope`` names that get a bucket of
  their own in the device-time split (`bench.hlo_scopes`);
* ``head(params)``: the ``[vocab, d]`` matrix the served logits are read
  against.

The family's equations are the plain reference's,
``bench/reference/<family>.py``.
"""
from __future__ import annotations

import importlib
import pathlib


def load(family: str):
    """The module of ``family``; a family with no module is an error that
    names the file looked for."""
    name = f"{__name__}.{family}"
    try:
        return importlib.import_module(name)
    except ModuleNotFoundError as e:
        if e.name != name:
            raise
        path = pathlib.Path(__file__).parent / f"{family}.py"
        raise ModuleNotFoundError(
            f"no family module for {family!r}: {path} does not exist",
            name=name) from None
