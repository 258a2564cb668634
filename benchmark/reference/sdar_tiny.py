"""The plain reference of ``sdar_share8`` at the settings of the tests'
tiny model: the same equations, heads of 16, two experts a token, blocks
of 4 revealed in 2 steps (``tiny(steps=4)``: in 4), the mask at row 31.
Never a measurement."""

from .sdar_share8 import PUBLISHED, make_forward, make_loss


def tiny(**changes) -> dict:
    return dict(
        PUBLISHED, head_dim=16, top_k=2, first_expert=0, mask_id=31,
        query_rows=8, expert_rows=8, head_rows=8, **changes,
    )


TINY = tiny()

forward = make_forward(TINY)
loss_fn = make_loss(TINY)
