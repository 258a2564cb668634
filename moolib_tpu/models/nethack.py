"""NetHack agent: glyph-embedding CNN + blstats MLP + LSTM core.

Driver benchmark config 5 ("R2D2-style LSTM policy on NetHack (NLE) —
recurrent rollout batching"). The reference repo itself ships no
NetHack model — its moolib-era NetHack work lived in a sibling project — so
this follows the standard NLE-baseline architecture shape: embed the glyph
grid, convolve it down, encode blstats with a small MLP, fuse, and run a
masked LSTM whose state is carried between unrolls by the actor loop
(:class:`moolib_tpu.examples.common.EnvBatchState` stores the core state at
each unroll boundary — the recurrent-rollout-batching half of R2D2; the
replay/burn-in half is off-policy machinery outside IMPALA's scope).

Same agent contract as every model in :mod:`moolib_tpu.models`:

    (logits_TBA, baseline_TB), state = net.apply(params, obs, done, state)

with ``obs`` the NLE-style dict {"glyphs": [T, B, 21, 79] int,
"blstats": [T, B, 27] float32}.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn

from ..ops.embed import embed_lookup
from .core import LSTMCore

__all__ = ["NetHackNet"]


class NetHackNet(nn.Module):
    num_actions: int = 23
    num_glyphs: int = 5976  # nle.nethack.MAX_GLYPH
    glyph_embed: int = 16
    blstats_size: int = 27
    hidden_size: int = 256
    use_lstm: bool = True
    lstm_size: int = 256
    compute_dtype: jnp.dtype = jnp.float32  # set jnp.bfloat16 on TPU

    @nn.compact
    def __call__(self, obs, done, core_state):
        glyphs, blstats = obs["glyphs"], obs["blstats"]
        T, B = glyphs.shape[:2]
        HH, WW = glyphs.shape[2:]

        # nn.Embed for the parameter (glyph_embed/embedding, its shape and
        # initialiser); the lookup is ours for its gradient (ops/embed.py)
        table = nn.Embed(
            self.num_glyphs, self.glyph_embed, name="glyph_embed"
        ).embedding
        g = embed_lookup(
            table, glyphs.astype(jnp.int32).reshape(T * B, HH, WW),
            self.compute_dtype,
        )
        for ch in (32, 64, 64):
            g = nn.relu(
                nn.Conv(ch, (3, 3), strides=(2, 2), dtype=self.compute_dtype)(g)
            )
        g = g.reshape(T * B, -1)

        # blstats are unbounded counters (HP, gold, turn count): squash.
        s = jnp.tanh(
            blstats.astype(self.compute_dtype).reshape(T * B, -1) * 0.01
        )
        s = nn.relu(nn.Dense(64, dtype=self.compute_dtype)(s))

        x = jnp.concatenate([g, s], axis=-1)
        x = nn.relu(nn.Dense(self.hidden_size, dtype=self.compute_dtype)(x))
        x = x.astype(jnp.float32).reshape(T, B, self.hidden_size)

        if self.use_lstm:
            x, core_state = LSTMCore(hidden_size=self.lstm_size)(
                x, done, core_state
            )

        policy_logits = nn.Dense(self.num_actions, name="policy")(x)
        baseline = nn.Dense(1, name="baseline")(x).squeeze(-1)
        return (policy_logits, baseline), core_state

    def initial_state(self, batch_size: int) -> Tuple:
        if self.use_lstm:
            z = jnp.zeros((batch_size, self.lstm_size), jnp.float32)
            return (z, z)
        return ()
