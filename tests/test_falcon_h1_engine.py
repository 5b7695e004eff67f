"""What of ``LLMEngine`` over ``models/falcon_h1.py`` is this family's alone
(what every family's engine owes: ``test_engine_families.py``; the model
itself: ``test_falcon_h1.py``): the weight migration's slabs, and the cell's
own check on wrong programs."""

import numpy as np
import pytest

from lfm2_toy import cell_check


@pytest.mark.parametrize('shape, itemsize, slab, transfers', [
    ((32, 4096, 14336), 2, 1, 32),  # a stacked kernel: an index a transfer
    ((6, 5120, 21504), 2, 1, 6),
    ((261120, 5120), 2, 6553, 40),  # the embedding: 64 MiB of rows
    ((5120, 261120), 2, 128, 40),  # the head beside it
    ((7, 3), 4, 7, 1),  # all of a small leaf at once
])
def test_a_leaf_of_many_small_rows_migrates_in_slabs(shape, itemsize, slab, transfers):
    """The weight migration's walk through the host (``_migrate_params``):
    one index of the first axis a transfer where that is a MiB or more,
    else slabs of one size that cover every row, the last laid over the end
    of the one before it (261,120 round trips for this family's embedding
    took 545 s of a cold set-up on the chip)."""
    from distllm_tpu.generate.engine.engine import bounce_slabs

    rows, nbytes = shape[0], int(np.prod(shape)) * itemsize
    got, starts = bounce_slabs(rows, nbytes)
    assert got == slab and len(starts) == transfers
    assert starts[0] == 0 and starts[-1] == rows - slab
    covered = np.zeros(rows, bool)
    for i in starts:
        assert 0 <= i <= rows - slab
        covered[i:i + slab] = True
    assert covered.all()
    assert slab == 1 or slab * (nbytes // rows) <= 64 << 20


@pytest.fixture(scope='module')
def probe_check():
    """The cell's own check (``drivers/falcon_h1_closed``) at toy size on an
    arm of ``scripts/probe_falcon_h1_reference.py``: ``arm -> result``."""
    return cell_check(
        'probe_falcon_h1_reference.py',
        'rehearsal_falcon_h1/configs/tiny-falcon-h1.json',
    )


# float32 on both sides at toy size: the right program reads 1e-6, so a
# fault shows in the reading the arm is about whatever the chip's limits are.
@pytest.mark.parametrize('arm, by, over', [
    ('no_mamba', 'token_gap_mean_std', 0.05),
    ('no_key_multiplier', 'kv_content_error', 0.1),
    ('no_ssm_multipliers', 'ssm_state_error', 0.05),
    ('group0', 'ssm_state_error', 0.1),
    ('no_rope', 'kv_content_error', 0.1),
    ('ssm_bf16', 'ssm_state_error', 1e-3),
    ('int8_kv', 'kv_content_error', 1e-3),
    ('no_zeroing', 'ssm_state_error', 0.01),
])
def test_the_cells_check_reads_a_wrong_program(probe_check, arm, by, over):
    right = probe_check('program')
    assert right['correct'] is True and right[by] < 1e-5
    wrong = probe_check(arm)
    assert wrong[by] > over
    if arm == 'no_zeroing':  # the second holders alone: the last two rows
        by_row = [e[0] for e in wrong['ssm_state_error_by_row']]
        assert max(by_row[:-2]) < 1e-5 < min(by_row[-2:])
