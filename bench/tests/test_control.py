"""The lower-precision control at a size a test run holds: the reference
computed with fp8 operands (one step below the configured bfloat16), put
in the program's place, comes out not correct by the same comparison that
passes the program's own bfloat16 served tokens.  On the chip, at the
cell's own size and limit, ``bench/calibrate.py`` reads the same
(PERF.md gives the readings and the limits set from them)."""
import pytest

from bench import run

from .smoke import BENCH, CONFIGS, SMOKE_LIMIT, cell_of, smoke_spec

MIX = {"kind": "decode", "batch": 8, "prompt_len": 16, "capacity": 48}


@pytest.mark.parametrize("arch", CONFIGS)
def test_control_reads_far_above_the_program(arch):
    spec = {**smoke_spec(arch, "bfloat16"),
            "limits": {"max_logit_gap": SMOKE_LIMIT}}
    r = run.run_cell(BENCH, cell_of(arch), 2**34 + 1, 1.0, False,
                     "TPU v5 lite", 1, spec=spec, mix=MIX, control=True)
    control = r["checks"]["max_logit_gap"]["value"]
    served = r["program_max_logit_gap"]
    assert r["correct"] is False, (control, served)
    assert served <= SMOKE_LIMIT < control
    assert control > 10 * served, (control, served)
