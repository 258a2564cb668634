from . import attention, embed, ring_attention, vtrace
from .batcher import Batcher

__all__ = ["vtrace", "attention", "ring_attention", "embed", "Batcher"]
