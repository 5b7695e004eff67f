"""What of ``LLMEngine`` over ``models/ouro.py`` is this family's alone (what
every family's engine owes: ``test_engine_families.py``; the model itself:
``test_ouro.py``): the exit rule below the published threshold, through the
decode window's counts."""

import numpy as np
import pytest

from benchmarks import reference_ouro as ref
from ouro_toy import make_engine
from test_engine_families import assert_teacher_forced, serve


@pytest.mark.parametrize('threshold', [0.3, 0.6])
def test_tokens_leave_early_below_the_published_threshold(threshold):
    """The engine follows the exit rule at any threshold: greedy tokens are
    the reference's at that threshold, and the windows count, pass by pass,
    the decoded tokens whose head read it."""
    hf, params, engine = make_engine(
        hf_over={'early_exit_threshold': threshold}
    )
    prompts, outputs, records = serve(
        'ouro', engine, 11, (9, 14), temperature=0.0, max_tokens=11
    )
    assert_teacher_forced('ouro', hf, params, prompts, outputs)
    windows = [r for r in records if r['kind'] == 'decode']
    exits = np.sum([r['loop_exit_pass'] for r in windows], axis=0)
    want = np.zeros(4, int)
    for p, o in zip(prompts, outputs):
        fed = np.asarray(list(p) + list(o)[:-1])[None]
        passes = ref.forward(params, hf, fed, [[0]])['exit_pass'][0]
        want += np.bincount(passes[len(p):], minlength=4)  # the decoded tokens'
    np.testing.assert_array_equal(exits, want)
    assert (want[:3] > 0).sum() >= 2  # tokens did leave early
