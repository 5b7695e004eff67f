"""What of ``LLMEngine`` over ``models/solar_open2.py`` is this family's
alone (what every family's engine owes: ``test_engine_families.py``; the
model itself: ``test_solar_open2.py``): the cell's own check on the wrong
programs its limits have to catch."""

import numpy as np
import pytest

from lfm2_toy import cell_check


@pytest.fixture(scope='module')
def probe_check():
    """The cell's own check (``drivers/solar_open2_closed``) at toy size on
    an arm of ``scripts/probe_solar_open2_reference.py``: ``arm -> result``."""
    return cell_check(
        'probe_solar_open2_reference.py',
        'rehearsal_solar_open2/configs/tiny-solar-open2.json',
    )


# float32 on both sides at toy size: the right program reads 1e-6, so a
# fault shows in the reading the arm is about whatever the chip's limits are.
# ``kda_equal_operand_error`` is the two forms of the program's recurrence
# against the reference's from equal operands: the reading that holds the
# recurrence to its precision.
@pytest.mark.parametrize('arm, by, over', [
    ('state_bf16', 'kda_equal_operand_error', 1e-3),
    ('beta_half', 'kda_state_error', 0.1),
])  # the other arms' faults: tests/test_solar_open2.py, on logits
def test_the_cells_check_reads_a_wrong_program(probe_check, arm, by, over):
    right = probe_check('program')
    assert right['correct'] is True
    assert max(right['kda_state_error']) < 1e-5 and right['kda_equal_operand_error'] < 1e-5
    assert len(right['kda_state_error']) == 3  # every KDA layer
    wrong = probe_check(arm)
    assert np.max(wrong[by]) > over
    if arm == 'state_bf16':  # what the stored bits say holds at any size
        assert wrong['correct'] is False and wrong['kda_state_bf16_share'] == 1.0
    if arm != 'state_bf16':  # nothing of the forms: equal operands, equal states
        assert wrong['kda_equal_operand_error'] < 1e-5
