"""The token driver's rehearsal cell with the timed path broken underneath
has to come out as not correct, once for each fault a one-chip training cell
can have; a sound run comes out correct. (``tests/test_language_model.py``
holds the sound run and the controls planted in the reference in tier-1; at
the cell's own size on the chip ``tools/readings_tokens.py`` judges them
against the cell's limits.)"""
import os

import pytest

from benchmarks import run as bench_run
from conftest import ROOT
from test_faults import half_batch, state_unchanged

BENCH = os.path.join(ROOT, "benchmarks", "tests", "bench_rehearse_tokens.json")


@pytest.mark.parametrize("fault", [None, state_unchanged, half_batch],
                         ids=["sound", "state_unchanged", "half_batch"])
def test_token_cell_judges_planted_faults(fault):
    line, _ = bench_run.execute("rehearse_glm_micro", 424242, 0.3, False,
                                require_tpu=False, bench_file=BENCH,
                                sabotage=fault)
    assert line["correct"] is (fault is None), line["compared"]
