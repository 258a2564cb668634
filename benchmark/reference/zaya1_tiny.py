"""The plain reference of ``zaya1_share8`` at the settings of the tests'
tiny model: the same equations, heads of 8 with 4 dimensions turned,
experts 0-1 of 4 held. Never a measurement."""

from .zaya1_share8 import PUBLISHED, make_forward, make_loss

TINY = dict(
    PUBLISHED, head_dim=8, rotary_dim=4, num_experts=4, first_expert=0,
    query_rows=8, head_rows=8,
)

forward = make_forward(TINY)
loss_fn = make_loss(TINY)
