"""The comparison that decides ``correct``.

A served token is judged by the plain float32 reference run over the same
prompt and the tokens that were fed back: the number compared is the
widest gap by which a served token's reference logit lies below the
reference's best at that position (0 where the served token is the
reference's own greedy choice).  Greedy traffic only, so every served
token is the program's argmax.
"""
from __future__ import annotations

import dataclasses
import importlib

import jax
import jax.numpy as jnp
import numpy as np

from ..weights import make_params
from .common import gaps

T_BUCKET = 256          # sequences are padded to a multiple of this
ROWS = 8                # sequences per reference call


@dataclasses.dataclass
class Served:
    """One request as served: ``tokens`` is the prompt followed by every
    token fed back; ``served[i]`` was emitted at position ``start + i``
    (so it is the program's choice after ``tokens[:start + i + 1]``)."""
    tokens: np.ndarray
    start: int
    served: np.ndarray


def hidden_fn(family: str):
    """``hidden(spec, params, tokens [B, T], lowp) -> [B, T, d]`` of the
    family's reference, ``bench/reference/<family>.py``."""
    mod = importlib.import_module(f"{__name__}.{family}")
    return jax.vmap(mod.hidden, in_axes=(None, None, 0, None))


def compare(spec: dict, seed: int, requests: list[Served],
            control: bool = False) -> dict:
    """Run the reference (and the fp8 control, if asked) over
    ``requests``; returns the widest served-token gap, the count of
    tokens checked and, with ``control``, the control's widest gap."""
    params = make_params(spec["model"], seed)
    fn = hidden_fn(spec["model"]["family"])
    t_max = max(len(r.tokens) for r in requests)
    t_pad = -(-t_max // T_BUCKET) * T_BUCKET
    run = jax.jit(lambda p, toks, lowp: fn(spec, p, toks, lowp),
                  static_argnums=2)
    head = jax.jit(gaps)
    served_gap, control_gap, n = [], [], 0
    with jax.default_matmul_precision("highest"):
        for i in range(0, len(requests), ROWS):
            chunk = requests[i:i + ROWS]
            toks = np.zeros((ROWS, t_pad), np.int32)
            for j, r in enumerate(chunk):
                toks[j, :len(r.tokens)] = r.tokens
            h_ref = run(params, jnp.asarray(toks), False)
            h_ctl = run(params, jnp.asarray(toks), True) if control \
                else h_ref
            for j, r in enumerate(chunk):
                pos = jnp.arange(r.start, r.start + len(r.served))
                sg, cg = head(h_ref[j][pos], h_ctl[j][pos], params["embed"],
                              jnp.asarray(r.served, jnp.int32))
                served_gap.append(np.asarray(sg))
                control_gap.append(np.asarray(cg))
                n += len(r.served)
    out = {"max_logit_gap": float(np.max(np.concatenate(served_gap))),
           "tokens_checked": n}
    if control:
        out["control_max_logit_gap"] = float(
            np.max(np.concatenate(control_gap)))
    return out
