from .a2c import A2CNet
from .core import LSTMCore
from .impala import (
    ConvSequence,
    ImpalaNet,
    ResidualBlock,
    space_to_depth,
    widen_impala_params,
)
from .lm import DecoderLM, decoder_lm
from .nethack import NetHackNet
from .transformer import TransformerNet

__all__ = [
    "A2CNet",
    "LSTMCore",
    "ConvSequence",
    "DecoderLM",
    "ImpalaNet",
    "NetHackNet",
    "ResidualBlock",
    "TransformerNet",
    "decoder_lm",
    "space_to_depth",
    "widen_impala_params",
]
