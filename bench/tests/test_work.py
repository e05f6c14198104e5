"""Required-work counts against hand arithmetic from the widths, and the
peak table."""
import json
import pathlib

import numpy as np
import pytest

from bench import work

CONFIGS = pathlib.Path(__file__).resolve().parents[1] / "configs"


def spec(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())


def test_olmo_token_flops_by_hand():
    # planned nonzeros per layer: wq/wk/wv/wo 2048 x 1024 each, w_gate/w_up
    # 8192 x 1024 each, w_down 2048 x 4096: 4*2097152 + 3*8388608
    nnz = 16 * (4 * 2048 * 1024 + 2 * 8192 * 1024 + 2048 * 4096)
    assert nnz == 536_870_912
    head = 50304 * 2048
    attn_per_ctx = 4 * 16 * 128 * 16
    got = work.token_flops(spec("olmo-1b"), [1, 1000])
    np.testing.assert_array_equal(
        got, [2 * (nnz + head) + attn_per_ctx,
              2 * (nnz + head) + 1000 * attn_per_ctx])


def test_olmo_spmm_step_work_by_hand():
    w = work.spmm_forward(spec("olmo-1b"), rows=8)
    assert w.flops == 2 * 8 * 536_870_912
    # bf16 nonzero values + bf16 activations in and out of all 7 matrices
    act = 16 * 8 * 2 * (4 * (2048 + 2048) + 3 * (2048 + 8192))
    assert w.bytes == 2 * 536_870_912 + act
    peak = work.peaks("TPU v5 lite")
    assert w.least_s(peak) == pytest.approx(w.bytes / 819e9)


def test_missing_device_kind_is_an_error():
    with pytest.raises(KeyError, match="not in"):
        work.peaks("TPU v9 imaginary")
    assert work.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
