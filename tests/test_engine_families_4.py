"""``test_engine_families.py`` over another family: a chain of its own under
``--dist loadfile``."""

from test_engine_families import *  # noqa: F401, F403 -- the cases themselves

FAMILIES = ('sdar',)
