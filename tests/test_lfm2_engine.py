"""What of ``LLMEngine`` over ``models/lfm2.py`` is this family's alone (what
every family's engine owes: ``test_engine_families.py``; the model itself:
``test_lfm2.py``): the cost model, and the cell's own check on rows that
hold another's state and pages."""

import jax
import pytest

from benchmarks import reference_lfm2 as ref
from lfm2_toy import cell_check, tiny


def test_roofline_counts_the_parameters_a_token_reaches():
    """A routed bank is priced at the share a token is multiplied by (3 of
    the router's 8 experts here); the dense layers' MLPs, which have no
    router beside them, and the tied embedding whole."""
    from distllm_tpu.observability.roofline import CostModel

    hf, cfg, params = tiny(0, num_experts=4, num_routed_experts=8)
    every = sum(x.size for x in jax.tree.leaves(params))
    banks = sum(params['sparse'][n]['kernel'].size for n in ('gate', 'up', 'down'))
    routed = CostModel.from_params(params, 4, experts_per_token=3)
    assert routed.n_params == pytest.approx(every - banks * (1 - 3 / 8))


@pytest.fixture(scope='module')
def probe_check():
    """The cell's own check (``drivers/lfm2_closed``) at toy size on an arm
    of ``scripts/probe_lfm2_reference.py``: ``arm -> result``."""
    return cell_check('probe_lfm2_reference.py', 'rehearsal_lfm2/configs/tiny-lfm2.json')


@pytest.mark.parametrize('arm, by, limit', [
    ('one_row', 'kv_content_error_max_row', ref.KV_ROW_LIMIT),
    ('one_row', 'state_content_error', ref.STATE_CONTENT_LIMIT),
    ('state_late', 'state_content_error', ref.STATE_CONTENT_LIMIT),
])
def test_the_cells_check_refuses_a_row_that_holds_anothers(
    probe_check, arm, by, limit
):
    """One scored row of eight that reads another row's slot and pages (a
    wrong slot, a wrong entry of one block table) fails the check by the
    limits on the LARGEST row, where the median over the rows passes it
    over; so does a state carried a token late, in every row."""
    right = probe_check('program')
    assert right['correct'] is True and right[by] < limit / 10
    wrong = probe_check(arm)
    assert wrong['correct'] is False and wrong[by] > 10 * limit
    if arm == 'one_row':
        assert wrong['kv_content_error'] < ref.KV_CONTENT_LIMIT / 10
