"""The decode loop's rewind on a full cache: the leaves with no sequence
axis (a recurrent state) get back their values from after the prefill,
the others are left alone, and a cache with no such leaf keeps nothing.
The device-time split still reads the step when the rewind's own module
runs in the window."""
import jax
import jax.numpy as jnp
import numpy as np

from bench import hlo_scopes, trace
from bench.loops import decode

BATCH, PROMPT, CAPACITY = 2, 6, 12


def bundle(arch):
    from repro.configs import get_smoke
    from repro.models import build_model
    return build_model(get_smoke(arch))


def names(init_cache, which):
    leaves = jax.tree_util.tree_flatten_with_path(
        jax.eval_shape(lambda: init_cache(BATCH, CAPACITY)))[0]
    return {jax.tree_util.keystr(leaves[i][0]) for i in which}


def test_only_recurrent_leaves_lack_a_sequence_axis():
    hybrid = bundle("zamba2-1.2b").init_cache
    assert names(hybrid, decode.recurrent_leaves(hybrid, BATCH, CAPACITY)) \
        == {"['ssm']", "['conv']"}
    dense = bundle("olmo-1b").init_cache
    assert decode.recurrent_leaves(dense, BATCH, CAPACITY) == ()
    assert decode.rewinder(dense(BATCH, CAPACITY), ()) == (None, None)


def test_restore_puts_back_the_state_after_the_prefill():
    b = bundle("zamba2-1.2b")
    params = b.init(jax.random.key(0))
    toks = np.random.default_rng(1).integers(
        0, b.cfg.vocab_size, (BATCH, PROMPT + 3)).astype(np.int32)
    _, pc = jax.jit(b.prefill)(params, {"tokens": toks[:, :PROMPT]})
    cache = decode.merge_prefill(b.init_cache(BATCH, CAPACITY), pc)
    after_prefill = jax.tree.map(np.asarray, cache)
    kept, restore = decode.rewinder(
        cache, decode.recurrent_leaves(b.init_cache, BATCH, CAPACITY))
    step = jax.jit(b.decode_step)
    for i in range(3):
        _, cache = step(params, {"tokens": toks[:, PROMPT + i:][:, :1],
                                 "cache_len": jnp.full((BATCH,), PROMPT + i,
                                                       jnp.int32)}, cache)
    before = jax.tree.map(np.asarray, cache)
    for name in ("ssm", "conv", "k", "v"):
        assert not np.array_equal(before[name], after_prefill[name]), name
    got = restore(cache, kept)
    for name in ("ssm", "conv"):
        np.testing.assert_array_equal(got[name], after_prefill[name])
    for name in ("k", "v"):
        np.testing.assert_array_equal(got[name], before[name])
    # the copy survives for the next pass
    again = restore(got, kept)
    np.testing.assert_array_equal(again["ssm"], after_prefill["ssm"])


def test_split_reads_the_module_the_window_ran_most():
    ns = 1_000_000
    host = trace.Plane("/host:CPU", {"python": [
        trace.Event(trace.WINDOW_SPAN, 0, 100 * ns)]})
    runs = [trace.Event("jit_step(1)", i * ns, (i + 1) * ns)
            for i in range(5)]
    runs.insert(3, trace.Event("jit_restore(2)", 3 * ns, 3 * ns + 10))
    runs.append(trace.Event("jit_step(1)", 200 * ns, 201 * ns))  # after
    dev = trace.Plane("/device:TPU:0", {hlo_scopes.MODULES_LINE: runs,
                                        trace.OPS_LINE: []})
    got = hlo_scopes.window_modules([host, dev])
    assert got == {"jit_step(1)": 5, "jit_restore(2)": 1}
    assert got.most_common(1)[0][0] == "jit_step(1)"
