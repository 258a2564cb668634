"""Runnable training examples — the "user training loop" layer.

Capability parity with the reference's examples tree
(reference: examples/{a2c.py, vtrace/experiment.py, atari/, common/}):

- :mod:`moolib_tpu.examples.a2c` — single-file A2C on CartPole with an
  in-process Broker + elastic Accumulator.
- :mod:`moolib_tpu.examples.vtrace` — the full elastic IMPALA/V-trace
  experiment: EnvPool acting with double buffering, learn batches
  assembled in place (``ops.batcher.LearnSlabs``),
  Accumulator-driven train/skip, leader checkpointing, global stats.
- :mod:`moolib_tpu.examples.envs` — environment factories (CartPole via
  gymnasium or a built-in numpy implementation; synthetic Atari-shaped
  pixels; real ALE when ale_py is installed).
- :mod:`moolib_tpu.examples.common` — rollout bookkeeping shared by the
  examples (EnvBatchState in-place time batching, tsv recording).

Nothing in this package is imported by the library proper; examples are
consumers of the public API only.
"""
