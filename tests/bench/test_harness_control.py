"""The control fails the comparison that passes the program.

At smoke size on the CPU: one seed's served requests are read as a run
reads them (the program's largest gap below the float32 reference) and as
the control reads them (the float32 gap of the token the float8 reference
puts first). The program keeps within the cell's rehearsal limit and the
control does not. The chip's limit, and the readings at the cell's own
size that it was set from, are in ``bench/limits/<cell>.json``."""

import pytest

from bench import calibrate, model, spec


@pytest.mark.parametrize("workload", ["yi9b-chat", "olmo1b-chat"])
def test_control_reads_over_the_limit_the_program_keeps(workload):
    cell = spec.cell(workload)
    m = model.flat(cell.config, rehearse=True)
    row = calibrate.reading(cell, m, 2**31 + 11, 3.0, "interpret")
    limit = cell.limits["rehearsal"]["max_logit_gap"]["limit"]
    assert row["program"] <= limit < row["control"]
