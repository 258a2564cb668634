"""The plain reference of ``mellum2_share8`` at the settings of the tests'
tiny model (``benchmark/tests/lm_tiny.py``): the same equations, a window
of 8, heads of 16, top-2, YaRN over an original context of 32. Never a
measurement."""

from .mellum2_share8 import PUBLISHED, make_forward

TINY = dict(
    PUBLISHED, layer_types=("sliding", "full"), head_dim=16, window=8,
    top_k=2, first_expert=2, query_rows=8,
    yarn=dict(PUBLISHED["yarn"], original=32),
)

forward = make_forward(TINY)
