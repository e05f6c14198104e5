"""The span recorder (`repro.obs`) and the plan build's phase spans."""
import jax
import pytest

from repro import obs


@pytest.fixture(autouse=True)
def clean_table():
    obs.reset()
    yield
    obs.reset()


def test_totals_count_and_sum_each_name():
    for _ in range(3):
        with obs.span("a", layer="wq"):
            pass
    with obs.span("b"):
        pass
    got = obs.totals()
    assert set(got) == {"a", "b"}
    assert got["a"][0] == 3 and got["b"][0] == 1
    assert got["a"][1] >= 0.0 and got["b"][1] >= 0.0


def test_a_span_that_raises_still_counts():
    with pytest.raises(ValueError):
        with obs.span("a"):
            raise ValueError("x")
    assert obs.totals()["a"][0] == 1


def test_reset_empties_the_table():
    with obs.span("a"):
        pass
    obs.reset()
    assert obs.totals() == {}


def test_plan_model_records_each_phase_once_per_projection():
    from repro.configs import get_smoke
    from repro.engine.plan import plan_model
    from repro.models import build_model
    cfg = get_smoke("olmo-1b")
    params = build_model(cfg).init(jax.random.key(0))
    plan = plan_model(cfg, params, sparsity=0.5, impl="pallas")
    got = obs.totals()
    phases = {k: v for k, v in got.items() if k.startswith("plan.")}
    assert set(phases) == {"plan.prune", "plan.blocks", "plan.pack",
                           "plan.encode"}
    assert len(plan.layers) == 7
    assert {n for n, _ in phases.values()} == {7}
