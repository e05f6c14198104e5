"""Small stand-ins for the benchmark's configurations (the repo's smoke
widths), for CPU tests."""
import dataclasses
import json
import pathlib

ROOT = pathlib.Path(__file__).resolve().parents[2]
# Logits at smoke width are about ten times smaller than at the cells'
# widths (the fp8 control reads 0.07-0.15 here, 0.59-0.72 on the chip
# against a limit of 0.16), so the smoke runs' limit is scaled alike.
SMOKE_LIMIT = 0.05


def smoke_spec(arch: str, dtype: str = "float32") -> dict:
    """The configuration file with the repo's smoke widths."""
    from repro.configs import get_smoke
    spec = json.loads((ROOT / "bench" / "configs" / f"{arch}.json")
                      .read_text())
    sm = dataclasses.asdict(get_smoke(arch))
    model = {k: sm[k] for k in spec["model"] if k in sm}
    model.update(param_dtype=dtype, compute_dtype=dtype, w_sparsity=0.5,
                 q_chunk=sm["q_chunk"], kv_chunk=sm["kv_chunk"])
    return {**spec, "model": model}
