"""The plain reference of ``glm47_flash_share8`` at the settings of the
tests' tiny model: the same equations, a head of 12 + 4 query/key and 16
value dimensions, top-2 of 8, experts 2-5 held. Never a measurement."""

from .glm47_flash_share8 import PUBLISHED, make_forward, make_loss

TINY = dict(
    PUBLISHED, qk_nope_head_dim=12, qk_rope_head_dim=4, v_head_dim=16,
    top_k=2, first_expert=2, query_rows=8, head_rows=16,
)

forward = make_forward(TINY)
loss_fn = make_loss(TINY)
