"""A run whose timed path is broken underneath comes out not correct.

Each cell's run is driven end to end at its rehearsal size on the CPU,
past the harness's look for a chip, with the cell's own limits: once as
it is (correct), once for every fault the cell can have (not correct):

* ``frozen``: a step that returns its state unchanged;
* ``half_batch``: half of every batch left out, the mean taken over the
  rest.

And the control: the reference, computed one precision step below what
the configuration states, put in the program's place, is not correct.

    JAX_PLATFORMS=cpu python3 -m pytest -q bench/tests/test_faults.py
"""
import pytest

import check
import harness

CELLS = {
    "wdl-criteo.celu-fp32": ("frozen", "half_batch"),
    "wdl-criteo.vanilla": ("frozen", "half_batch"),
    "smollm-360m.celu-int8": ("frozen", "half_batch"),
}
ARGS = ["--seed", "3000000019", "--seconds", "0.5", "--rehearse"]


def _run(cell, fault=""):
    return harness.main(["--workload", cell] + ARGS, fault=fault)


@pytest.mark.parametrize("cell,fault", [(c, f) for c, fs in CELLS.items()
                                        for f in ("",) + fs])
def test_fault_is_caught(cell, fault):
    out = _run(cell, fault)
    assert out["rehearsal"] is True and "correct" not in out
    assert out["would_be_correct"] is (fault == ""), out["checks"]


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_control_is_not_correct(cell):
    c = harness.Cell(cell, rehearse=True)
    harness.configure_jax(True, c.chips)
    run = c.driver.Run(c, 3000000023)
    run.release()
    ref = run.reference()
    numbers = run.numbers(ref=ref, prog=run.reference(control=True))
    _, ok = check.judge(numbers, c.limits)
    assert not ok, numbers
