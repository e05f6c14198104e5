"""A whole run on the CPU, past the harness's look for a chip, with the
timed path broken underneath: ``correct`` has to come out false for each
fault a decode cell can have, and true without one."""
import contextlib

import pytest

from bench import faults, run

from .smoke import BENCH, CONFIGS, SMOKE_LIMIT, cell_of, smoke_spec

MIX = {"kind": "decode", "batch": 4, "prompt_len": 8, "capacity": 16}


def run_smoke(arch, fault, seconds=0.5):
    spec = {**smoke_spec(arch), "limits": {"max_logit_gap": SMOKE_LIMIT}}
    plant = faults.planted(fault) if fault else contextlib.nullcontext()
    with plant:
        return run.run_cell(BENCH, cell_of(arch), 2**35 + 3, seconds,
                            False, "TPU v5 lite", 1, spec=spec, mix=MIX)


@pytest.mark.parametrize("fault,correct", [
    (None, True), ("unchanged_state", False), ("half_batch", False),
    ("altered_token", False)])
@pytest.mark.parametrize("arch", CONFIGS)
def test_broken_timed_path_is_not_correct(arch, fault, correct):
    r = run_smoke(arch, fault)
    gap = r["checks"]["max_logit_gap"]
    assert r["correct"] is correct, gap


def test_window_starts_over_when_the_cache_fills():
    # 8 free cache rows: a 2 s window at smoke width makes many passes,
    # and the first (checked) pass is still correct, in every
    # configuration.
    for arch in CONFIGS:
        r = run_smoke(arch, None, seconds=2.0)
        assert r["attempted"] > MIX["batch"] \
            and r["attempted"] % MIX["batch"] == 0, arch
        assert r["correct"], (arch, r["checks"])
