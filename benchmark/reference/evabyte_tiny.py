"""The plain reference of ``evabyte_pp8`` at the settings of the tests'
tiny model: the same equations, heads of 8, windows of 8 bytes in chunks
of 2, a vocabulary of 20. Never a measurement."""

from .evabyte_pp8 import PUBLISHED, make_forward, make_loss

TINY = dict(
    PUBLISHED, head_dim=8, window_size=8, chunk_size=2, vocab_size=20,
    query_rows=8, head_rows=8, mlp_rows=8,
)

forward = make_forward(TINY)
loss_fn = make_loss(TINY)
