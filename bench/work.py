"""The work a configuration requires, counted from its widths and its
sparsity alone, never from how the program lays the weights out.

* A planned projection ``[n_in, n_out]`` at zero fraction ``s`` holds
  ``n_out * keep(n_in, s)`` nonzeros; one row through it costs 2 FLOPs
  per nonzero.  Its required bytes are the nonzero values at the weight
  dtype plus the activations in and out at the compute dtype.
* Dense matmuls cost 2 FLOPs per weight per row.
* Attention at context ``c`` costs ``4 * c * H * dh`` per token and
  layer (scores and the weighted sum).

Elementwise work (norms, activations, rotary, convs) is left out: it is
bounded by the matmuls at these widths.
"""
from __future__ import annotations

import dataclasses
import json
import pathlib

import numpy as np

PEAKS_FILE = pathlib.Path(__file__).resolve().parent / "peaks.json"

_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4, "int8": 1}


def peaks(device_kind: str) -> dict:
    """The peak table row of ``device_kind``; a device that is not in
    the table is an error."""
    table = json.loads(PEAKS_FILE.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in "
                       f"{PEAKS_FILE.name} ({sorted(table)})")
    return table[device_kind]


def keep(n_in: int, sparsity: float) -> int:
    return max(1, min(n_in, int(round(n_in * (1.0 - sparsity)))))


def projections(m: dict) -> dict:
    """name -> (n_in, n_out, copies per forward) of every weight matrix."""
    d, f = m["d_model"], m["d_ff"]
    qd, kd = m["n_heads"] * m["head_dim"], m["n_kv_heads"] * m["head_dim"]
    attn = {"wq": (d, qd), "wk": (d, kd), "wv": (d, kd), "wo": (qd, d)}
    mlp = {"w_gate": (d, f), "w_up": (d, f), "w_down": (f, d)}
    if m["family"] != "dense":
        raise ValueError(f"no work model for family {m['family']!r}")
    return {k: (*v, m["n_layers"]) for k, v in {**attn, **mlp}.items()}


@dataclasses.dataclass(frozen=True)
class Work:
    flops: float
    bytes: float

    def least_s(self, peak: dict) -> float:
        """Least time on a chip: compute- or bandwidth-bound, whichever
        is larger."""
        return max(self.flops / peak["bf16_flops_per_s"],
                   self.bytes / peak["hbm_bytes_per_s"])


def spmm_forward(spec: dict, rows: int) -> Work:
    """Required work of every planned projection of one forward pass
    over ``rows`` rows (tokens)."""
    m = spec["model"]
    wb = _BYTES[m["param_dtype"]]
    ab = _BYTES[m["compute_dtype"]]
    shapes = projections(m)
    flops = byts = 0.0
    for name in spec["planned"]:
        n_in, n_out, copies = shapes[name]
        nnz = n_out * keep(n_in, m["w_sparsity"])
        flops += copies * 2.0 * rows * nnz
        byts += copies * (nnz * wb + rows * (n_in + n_out) * ab)
    return Work(flops, byts)


def token_flops(spec: dict, context) -> np.ndarray:
    """Required FLOPs of one token at each context length in ``context``
    (the positions it attends over, itself included)."""
    m = spec["model"]
    planned = set(spec["planned"])
    per = 0.0
    for name, (n_in, n_out, copies) in projections(m).items():
        k = keep(n_in, m["w_sparsity"]) if name in planned else n_in
        per += copies * 2.0 * n_out * k
    per += 2.0 * m["vocab_size"] * m["d_model"]               # LM head
    attn = 4.0 * m["n_heads"] * m["head_dim"] * m["n_layers"]
    return per + attn * np.asarray(context, np.float64)
