"""A family's weight draw, work counts, scopes and logits head come from
``bench/families/<family>.py``, found by name: the dense draw is pinned to
the values it had before it moved there, and a family the harness has
never seen comes in by its module alone."""
import json
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import families, hlo_scopes, work
from bench.weights import make_params, normal, split

from .smoke import ROOT, smoke_spec
from .test_hlo_scopes import comp, instr, msg

# float64 sum and sum of squares of every leaf of the olmo-1b smoke draw in
# bfloat16 at seed 2**33 + 17, read from the draw before it moved into
# `bench/families/dense.py`.
DENSE_SMOKE = {
    "['blocks']['attn_norm']": (128.0, 128.0),
    "['blocks']['mlp_norm']": (128.0, 128.0),
    "['blocks']['w_down']": (-6.479201674461365, 129.1582820143285),
    "['blocks']['w_gate']": (-9.373174414038658, 253.0098622828516),
    "['blocks']['w_up']": (-11.818757832050323, 255.00192970074778),
    "['blocks']['wk']": (12.699563026428223, 129.4897277574405),
    "['blocks']['wo']": (4.216165151447058, 130.48200632683216),
    "['blocks']['wq']": (-14.481010437011719, 127.00235058556248),
    "['blocks']['wv']": (12.072455048561096, 126.78507061886752),
    "['embed']": (3.423573598265648, 6.491328855857587),
    "['final_norm']": (64.0, 64.0),
}
# The full-width olmo-1b tree, as served.
DENSE_FULL = {
    "['blocks']['attn_norm']": (16, 2048),
    "['blocks']['mlp_norm']": (16, 2048),
    "['blocks']['w_down']": (16, 8192, 2048),
    "['blocks']['w_gate']": (16, 2048, 8192),
    "['blocks']['w_up']": (16, 2048, 8192),
    "['blocks']['wk']": (16, 2048, 2048),
    "['blocks']['wo']": (16, 2048, 2048),
    "['blocks']['wq']": (16, 2048, 2048),
    "['blocks']['wv']": (16, 2048, 2048),
    "['embed']": (50304, 2048),
    "['final_norm']": (2048,),
}


def by_path(tree) -> dict:
    return {jax.tree_util.keystr(p): x
            for p, x in jax.tree_util.tree_flatten_with_path(tree)[0]}


def test_dense_draw_is_pinned():
    spec = smoke_spec("olmo-1b", "bfloat16")
    got = {}
    for path, x in by_path(make_params(spec["model"], 2**33 + 17)).items():
        assert x.dtype == jnp.bfloat16, path
        a = np.asarray(x, np.float64)
        got[path] = (float(a.sum()), float((a * a).sum()))
    assert got == DENSE_SMOKE
    full = json.loads((ROOT / "bench" / "configs" / "olmo-1b.json")
                      .read_text())
    shapes = by_path(jax.eval_shape(lambda: make_params(full["model"], 7)))
    assert {k: x.shape for k, x in shapes.items()} == DENSE_FULL
    assert {x.dtype for x in shapes.values()} == {jnp.dtype(jnp.bfloat16)}


def _toy_module():
    """A family of one 'mixer' projection per layer plus an attention
    block that a forward calls twice."""
    toy = types.ModuleType("bench.families.toy")
    toy.SCOPES = ("mixer", "attention")

    def draw(m, keys):
        ks = split(keys, 2)
        return {"blocks": {"mix": normal(ks[0], (m["n_layers"],
                                                 m["d_model"],
                                                 m["d_model"]))},
                "unembed": normal(ks[1], (m["vocab_size"], m["d_model"]))}

    toy.draw = draw
    toy.projections = lambda m: {
        "mix": (m["d_model"], m["d_model"], m["n_layers"]),
        "wq": (m["d_model"], 16, 2)}
    toy.attention_flops = lambda m: 4.0 * 16 * 2
    toy.head = lambda params: params["unembed"]
    return toy


def test_a_new_family_comes_in_by_its_module_alone(monkeypatch):
    monkeypatch.setitem(sys.modules, "bench.families.toy", _toy_module())
    m = {"family": "toy", "n_layers": 3, "d_model": 8, "vocab_size": 32,
         "w_sparsity": 0.5, "param_dtype": "bfloat16",
         "compute_dtype": "bfloat16"}
    spec = {"model": m, "planned": ["mix"]}

    params = make_params(m, 2**33 + 1)
    assert params["blocks"]["mix"].shape == (3, 8, 8)
    assert families.load("toy").head(params).shape == (32, 8)

    # mix: 3 copies of 8 x keep(8) = 8 x 4 nonzeros; wq dense, 2 copies
    per = 2 * (3 * 8 * 4 + 2 * 16 * 8) + 2 * 32 * 8
    np.testing.assert_array_equal(work.token_flops(spec, [1, 10]),
                                  [per + 128, per + 1280])
    w = work.spmm_forward(spec, rows=2)
    assert w.flops == 3 * 2 * 2 * 8 * 4
    assert w.bytes == 3 * (8 * 4 * 2 + 2 * (8 + 8) * 2)

    module = msg((1, msg((1, "jit_step"), (3, comp(
        1, "body",
        instr("ssd.1", "reduce", "jit(step)/mixer/reduce"),
        instr("dot.1", "dot", "jit(step)/attention/dot_general"),
        instr("spmm.1", "custom-call", "jit(step)/mixer/pallas_call",
              kernel="balanced_spmm_skinny"),
        instr("add.1", "add", "jit(step)/add"))))))
    scopes = families.load("toy").SCOPES
    att = hlo_scopes.attribute(module, scopes)
    assert att == {"ssd.1": "mixer", "dot.1": "attention",
                   "spmm.1": "balanced_spmm", "add.1": "unattributed"}
    out = hlo_scopes.split({"%ssd.1 = f32[8] reduce": 2.0,
                            "%dot.1 = f32[8] dot": 1.0,
                            "%spmm.1 = f32[8] custom-call": 4.0,
                            "%add.1 = f32[8] add": 0.5}, att, scopes)
    assert out == {"balanced_spmm": 4.0, "kv_update": 0.0, "mixer": 2.0,
                   "attention": 1.0, "unattributed": 0.5}


def test_an_unknown_family_names_the_file_it_looked_for():
    with pytest.raises(ModuleNotFoundError,
                       match=r"bench/families/no_such_family\.py"):
        make_params({"family": "no_such_family"}, 1)
    with pytest.raises(ModuleNotFoundError, match="no_such_family"):
        work.token_flops({"model": {"family": "no_such_family"},
                          "planned": []}, [1])
