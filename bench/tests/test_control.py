"""The control: the plain reference computed in float8 e4m3 (the step
below the bf16 the configuration states), put in the program's place,
must fail the cell's limits."""

from bench import harness
from bench.tests import tiny

harness.program_on_path()

SEED = 4242424242


def test_train_control_fails_a_limit():
    from bench.drivers import train
    cell = tiny.cell("granite-train-1chip")
    job, cfg = cell.traffic, cell.config
    phases = [[] for _ in range(job["period"])]
    ref = train.reference_readings(cfg, job, SEED, phases)
    ctrl = train.reference_readings(cfg, job, SEED, phases, prec="fp8")
    got = train.compare(ctrl, ref)
    assert any(v > cell.limits[k]["limit"] for k, v in got.items()), got
