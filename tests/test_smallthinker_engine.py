"""What of ``LLMEngine`` over ``models/smallthinker.py`` is this family's
alone (what every family's engine owes: ``test_engine_families.py``; the
model itself: ``test_smallthinker.py``): the step's price."""

import jax
import pytest

from smallthinker_toy import make_engine


def test_the_cost_model_prices_a_step_of_held_experts_without_the_embedding():
    """Bytes: everything held but the embedding (gathered by row). FLOPs:
    of the banks only the share a token reaches, 3 of the 8 the router
    ranks, whatever is held."""
    hf, params, engine = make_engine(
        hf_over=dict(moe_num_primary_experts=4, num_routed_experts=8)
    )
    size = lambda tree: sum(a.size for a in jax.tree.leaves(tree))  # noqa: E731
    banks = sum(size(params['sparse'][n]) for n in ('gate', 'up', 'down'))
    rest = size(params) - size(params['embed']) - banks
    model = engine._cost_model
    assert model.weight_bytes == 4 * (rest + banks)  # float32 toy
    assert model.n_params == pytest.approx(rest + banks * 3 / 8)
