"""The plain reference of ``solar_open2_share8`` at the settings of the
tests' tiny model: the same equations, heads of 16, top-2 of 16, experts
4-7 held. Never a measurement."""

from .solar_open2_share8 import PUBLISHED, make_forward, make_loss

TINY = dict(
    PUBLISHED, head_dim=16, top_k=2, first_expert=4, query_rows=8,
    scan_rows=8, head_rows=8,
)

forward = make_forward(TINY)
loss_fn = make_loss(TINY)
