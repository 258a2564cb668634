"""``lib/counts_kda.py``: the boundary counts against the program's own
(``models/lm.py:kda_boundary_counts``), the FLOPs of the recurrence as
written, the parameters against the program's shapes, and the parts of
``solar_open2_share8``'s step against the issue's arithmetic."""

import json
import os

import numpy as np
import pytest

from helpers import REPO

from benchmark.lib import counts_kda, peaks


def model():
    with open(os.path.join(
            REPO, "benchmark", "configs", "solar_open2_share8.json")) as f:
        return json.load(f)["model"]["kwargs"]


@pytest.mark.parametrize("T,done_at", [
    (4096, ()), (4096, (0,)), (4096, (64, 128)), (4096, (63, 65, 100, 4095)),
    (200, (64, 191)), (40, (13, 27)), (5, (0, 4)),
])
def test_the_boundary_counts_are_the_programs(T, done_at):
    import jax.numpy as jnp

    from moolib_tpu.models.lm import kda_boundary_counts

    done = np.zeros(T, bool)
    done[list(done_at)] = True
    got = counts_kda.boundary_counts(model(), done)
    seg = jnp.asarray(np.cumsum(done)[None], jnp.int32)
    want = {k: 3 * int(v) for k, v in kda_boundary_counts(seg).items()}
    assert got == want
    assert got["kda_state_resets"] == 3 * len(done_at)


def test_a_boundary_on_a_chunks_edge_cuts_no_chunk():
    done = np.zeros(4096, bool)
    done[[64, 640]] = True
    assert counts_kda.boundary_counts(model(), done)["kda_chunks_cut"] == 0
    done[[65, 700, 701]] = True  # two chunks, one of them twice
    assert counts_kda.boundary_counts(model(), done)["kda_chunks_cut"] == 6


def test_the_recurrence_is_counted_as_written():
    m = model()
    delta = m["attention_kinds"]["kda"]["delta"]
    # a position and head: the decay, S^T k, the rank-one update, the read
    assert counts_kda.core_flops(delta) == 8 * 7 * 128 * 128
    parts = counts_kda.forward_flops(m, 4096, 3277.0, np.zeros(4096, bool))
    assert parts["kda_core"] == 3 * 4096 * 8 * 7 * 128 * 128
    least = counts_kda.core_least(m, parts, 4096, peaks.peaks("TPU v5 lite"))
    assert least["flops"] == 3 * parts["kda_core"]
    # q, k, v, g of 128, beta and o of 128: 5 x 128 + 1 numbers a
    # position and head, float32, forward, backward and the cotangents
    assert least["least_bytes"] == 3 * 4096 * 8 * 641 * 4 * 3
    assert least["bound_by"] == "bytes"
    assert least["least_seconds"] < 1e-3


def test_the_steps_flops_by_part_are_the_issues():
    m = model()
    done = np.zeros(4096, bool)
    parts = counts_kda.forward_flops(m, 4096, 4 * 819.2, done)
    total = counts_kda.train_flops(parts)
    # the issue's 6.1 model TFLOP a step, and a little over where one
    # episode fills the softmax layer's whole triangle
    assert 6.0e12 < total < 6.4e12
    assert 0.39 < 3 * parts["head"] / total < 0.41  # the head 40% of it
    assert parts["kda_core"] / sum(
        v for k, v in parts.items() if k != "attention_tiles") < 0.03
    # one episode: every pair below the diagonal, every tile of 36
    assert parts["attention_pairs"] == 4 * 128 * 8 * 4096 * 4097 // 2
    assert parts["attention_tiles"] == 4 * 128 * 8 * 36 * 512 * 512
    assert "attention_tiles" not in [
        k for k in parts if k != "attention_tiles"]


def test_the_parameters_are_the_programs():
    import jax

    from benchmark.lib import program, seeded_kda

    with open(os.path.join(
            REPO, "benchmark", "configs", "solar_open2_share8.json")) as f:
        config = json.load(f)
    shapes = seeded_kda.param_shapes(program.build_model(config))
    assert counts_kda.parameters(config["model"]["kwargs"]) == sum(
        x.size for x in jax.tree_util.tree_leaves(shapes)) == 840_876_697
