"""The plain reference of ``xing4_share8`` at the settings of the tests'
tiny model: the same equations, a head of 12 + 4 query/key and 8 value
dimensions, YaRN over an original context of 16, top-2 of 8, experts 2-5
held. Never a measurement."""

from .xing4_share8 import PUBLISHED, make_forward, make_loss

TINY = dict(
    PUBLISHED, qk_nope_head_dim=12, qk_rope_head_dim=4, v_head_dim=8,
    yarn_factor=4.0, yarn_original=16, top_k=2, first_expert=2,
    query_rows=8, head_rows=16,
)

forward = make_forward(TINY)
loss_fn = make_loss(TINY)
