"""A whole run of the harness on the CPU at the rehearsal size, with the timed
path broken underneath, has to come out as not correct, once for each fault a
one-chip training cell can have and in each family's rehearsal cell; and so has
the control (the reference in fp8 in the program's place). A sound run comes
out correct. (At the cells' own size on the chip ``tools/readings.py`` judges
the same control and faults against the cells' limits.)"""
import jax
import jax.numpy as jnp
import pytest

from benchmarks import run as bench_run
from benchmarks.harness import check
from benchmarks.references import train_ref
from conftest import BENCH_REHEARSE

CELLS = ["rehearse_vit_micro", "rehearse_swin_t"]
SEED = 424242


def state_unchanged(trainer):
    """The step computes its metrics and hands back the state it was given."""
    real = trainer.train_step

    def step(state, batch, rng):
        _, metrics = real(jax.tree.map(jnp.copy, state),
                          jax.tree.map(jnp.copy, batch), rng)
        return state, metrics
    trainer.train_step = step


def half_batch(trainer):
    """Half of the batch left out, the mean taken over the rest: the first
    half stands in for the second, so the mean is the first half's."""
    real = trainer.train_step

    def step(state, batch, rng):
        half = jax.tree.map(lambda x: jnp.concatenate(
            [x[: x.shape[0] // 2]] * 2), batch)
        return real(state, half, rng)
    trainer.train_step = step


def _run(cell, sabotage=None):
    line, run = bench_run.execute(cell, SEED, 0.3, False, require_tpu=False,
                                  bench_file=BENCH_REHEARSE, sabotage=sabotage)
    return line, run


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct_and_control_is_not(cell):
    line, run = _run(cell)
    assert line["correct"] is True, line["compared"]
    batches, keeps = run.reference_inputs
    fam = train_ref.family(run.config["family"])
    out = train_ref.follow(
        fam_name=run.config["family"], shapes=run.config["shapes"],
        recipe=run.config["recipe"], batches=batches, keeps=keeps,
        rows=run.traffic["reference_rows"], mode="fp8",
        params=train_ref.make_params(fam.param_spec(run.config["shapes"]), SEED))
    numbers = check.compare({k: out[k] for k in run.program}, run.reference)
    numbers["rows_wrong"] = 0.0
    ok, rows = check.judge(numbers, run.checks["limits"])
    assert not ok, rows


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("fault", [state_unchanged, half_batch])
def test_planted_fault_is_not_correct(fault, cell):
    line, _ = _run(cell, sabotage=fault)
    assert line["correct"] is False, line["compared"]
