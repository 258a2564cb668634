"""The decoder under block diffusion (``DecoderLM`` with ``diffusion``),
whose action is a denoising step: the program's one pass over the clean
sequence and its masked copies against what a step-by-step sampler
computes, against the plain reference (``benchmark/reference/sdar_*``),
against a dense attention over the visibility rule written as a matrix,
and the configuration's count. Small sizes on the CPU; held to two minutes.
"""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark.lib import program, reference_latent  # noqa: E402
from benchmark.lib import reference_train, seeded_sdar  # noqa: E402
from benchmark.reference import sdar_share8 as reference  # noqa: E402
from benchmark.reference import sdar_tiny  # noqa: E402
from moolib_tpu.learner import (ImpalaConfig, impala_loss,  # noqa: E402
                                make_impala_train_step, make_train_state)
from moolib_tpu.models import lm  # noqa: E402
from moolib_tpu.models.lm import decoder_lm, learn_apply  # noqa: E402
from moolib_tpu.ops import attention as attn_ops  # noqa: E402
from moolib_tpu.parallel.moe import linear_scores, moe_dropless  # noqa: E402

D, V, MASK = 4, 32, 31
LOSS = {"discounting": 0.99, "baseline_cost": 0.5, "entropy_cost": 0.0006,
        "reward_clip": 1.0}
OPTIMIZER = {"grad_clip": 40.0, "learning_rate": 0.0006, "decay": 0.99,
             "eps": 0.01}
CAST = reference_train.identity_cast


def tiny(S: int, **changes):
    """2 layers, hidden 64, 4 / 2 heads of 16, 8 experts of which 2 are
    held, blocks of 4 revealed in ``S`` steps."""
    model = dict(
        vocab_size=V, hidden_size=64,
        layers=[{"attention": "blockdiff", "mlp": "sparse", "repeat": 2}],
        attention_kinds={"blockdiff": {
            "window": None, "rope": {"theta": 1e6}, "qk_norm": True}},
        diffusion={"block": D, "steps": S, "mask_id": MASK},
        num_heads=4, num_kv_heads=2, head_dim=16, num_experts=8,
        experts_held=[0, 2], top_k=2, moe_intermediate_size=32,
    )
    model.update(changes)
    return decoder_lm(**model), model


def seeded(net, model, seed: int):
    return seeded_sdar.make_params(seeded_sdar.param_shapes(net), seed, model)


def reveal_steps(rng, S: int, blocks: int, B: int) -> np.ndarray:
    """[D (blocks + 1), B]: every step of every acted block reveals a
    token, in uneven sets where S < D."""
    out = np.full((D * (blocks + 1), B), S, np.int64)
    for b in range(B):
        for n in range(blocks):
            r = rng.integers(0, S, D)
            r[rng.permutation(D)[:S]] = np.arange(S)  # no step is empty
            out[D * n:D * (n + 1), b] = r
    return out


def learn_batch(S: int, blocks: int, B: int, seed: int, boundaries=()):
    """A learn batch with two time axes; an episode begins at the first
    step of each block in ``boundaries``."""
    rng = np.random.default_rng(seed)
    L, steps, acted = D * (blocks + 1), S * blocks, D * blocks
    tokens = rng.integers(0, MASK, (L, B))
    reveal = reveal_steps(rng, S, blocks, B)
    done = np.zeros((steps + 1, B), bool)
    for b in boundaries:
        done[S * b] = True
    step = S * (np.arange(acted)[:, None] // D) + reveal[:acted]
    return {
        "obs": {"tokens": jnp.asarray(tokens, jnp.int32),
                "reveal_step": jnp.asarray(reveal, jnp.int32)},
        "done": jnp.asarray(done),
        "rewards": jnp.asarray(rng.normal(size=(steps + 1, B)), jnp.float32),
        "actions": jnp.asarray(tokens[:acted], jnp.int32),
        "action_step": jnp.asarray(step, jnp.int32),
        "behavior_logits": jnp.asarray(
            rng.normal(size=(acted, B, V)), jnp.float32),
        "core_state": (),
    }


# ------------------------------------------- one pass against generation

def sample_trajectory(params, spec, S: int, blocks: int, boundaries, seed):
    """A plain step-by-step sampler over one sequence: block by block, the
    model (the reference's stack, plain ``jax.numpy``) run on ``[finished
    blocks ; the current block as it stands]`` under a block-causal mask
    within the episode (so a finished block's keys are those of its clean
    pass), ``S`` steps a block; a step reveals, of the positions still
    masked, those the policy is surest of (two a step at S = 2, sometimes
    one or three; one a step at S = 4) and samples their tokens. Returns
    the tokens, the reveal steps, and of every revealed token the logits
    and the value of the state it was revealed from; block ``blocks`` stays
    masked and gives its four values."""
    p = params["params"]
    rng = np.random.default_rng(seed)
    tokens = np.full(D * (blocks + 1), MASK, np.int64)
    reveal = np.full(D * (blocks + 1), S, np.int64)
    logits_of = np.zeros((D * blocks, V), np.float32)
    values = np.zeros(D * (blocks + 1), np.float32)
    episode = np.cumsum([b in boundaries for b in range(blocks + 1)])

    block = np.arange(len(tokens)) // D
    seen = jnp.asarray(
        (episode[block][:, None] == episode[block][None, :])
        & (block[None, :] <= block[:, None])
    )

    @jax.jit
    def model(ids):
        h = reference.stack(
            p, ids, jnp.arange(len(tokens)),
            lambda start, rows: jax.lax.dynamic_slice_in_dim(
                seen, start, rows),
            dict(spec, query_rows=D), CAST,
        )
        x = reference.rms(h, p["final_norm"]["scale"], spec["eps"])
        return (reference.dot(x, p["head"]["kernel"], CAST),
                reference.value(x, p, CAST))

    def run(upto):
        """The model on the first ``upto`` tokens as they stand. The rows
        after them ride along (one program for every step): under a
        block-causal mask no row reads a later block."""
        logits, value = model(jnp.asarray(tokens))
        return np.asarray(logits)[:upto], np.asarray(value)[:upto]

    for b in range(blocks + 1):
        here = np.arange(D * b, D * (b + 1))
        if b == blocks:  # the bootstrap frame: all masked, values alone
            values[here] = run(D * (b + 1))[1][here]
            break
        for tau in range(S):
            logits, value = run(D * (b + 1))
            masked = here[tokens[here] == MASK]
            left = S - tau - 1  # steps still to come, a token each at least
            count = len(masked) if left == 0 else int(np.clip(
                rng.integers(1, 4), 1, len(masked) - left))
            sure = np.max(jax.nn.log_softmax(logits[masked]), axis=-1)
            for i in masked[np.argsort(-sure)[:count]]:
                probs = np.asarray(jax.nn.softmax(logits[i, :MASK]))
                tokens[i] = rng.choice(MASK, p=probs / probs.sum())
                reveal[i], logits_of[i], values[i] = tau, logits[i], value[i]
    return tokens, reveal, logits_of, values


@pytest.mark.parametrize("S", [2, 4])
def test_one_pass_gives_what_the_sampler_computed_step_by_step(S):
    """The training pass is held to generation: over a trajectory the
    sampler recorded (two episodes), the program's one pass over the clean
    sequence and its copies gives the sampler's logits and values at every
    scored token, and with the sampler's logits as the behaviour policy
    every step's log importance ratio is zero."""
    blocks, boundaries = 5, (3,)
    net, model = tiny(S)
    params = seeded(net, model, 11)
    spec = sdar_tiny.tiny(steps=S)
    with jax.default_matmul_precision("highest"):
        tokens, reveal, logits_of, values = sample_trajectory(
            params, spec, S, blocks, boundaries, seed=5)
    acted = D * blocks
    assert all(
        set(reveal[D * b:D * (b + 1)]) == set(range(S)) for b in range(blocks)
    )
    done = np.zeros((S * blocks + 1, 1), bool)
    done[S * boundaries[0]] = True
    batch = {
        "obs": {"tokens": jnp.asarray(tokens[:, None], jnp.int32),
                "reveal_step": jnp.asarray(reveal[:, None], jnp.int32)},
        "done": jnp.asarray(done),
        "rewards": jnp.ones((S * blocks + 1, 1), jnp.float32),
        "actions": jnp.asarray(tokens[:acted, None], jnp.int32),
        "action_step": jnp.asarray(
            (S * (np.arange(acted) // D) + reveal[:acted])[:, None],
            jnp.int32),
        "behavior_logits": jnp.asarray(logits_of[:, None]),
        "core_state": (),
    }
    with jax.default_matmul_precision("highest"):
        (logits, baseline), _ = jax.jit(net.apply)(
            params, batch["obs"], batch["done"], ())
        np.testing.assert_allclose(logits[:, 0], logits_of, atol=2e-5)
        np.testing.assert_allclose(baseline[:, 0], values, atol=2e-5)

        seen = {}

        def spy(*args, **kwargs):
            seen["vt"] = real(*args, **kwargs)
            return seen["vt"]

        from moolib_tpu.ops import vtrace
        real, vtrace.from_grouped_logits = vtrace.from_grouped_logits, spy

        def ratios(p, b):  # what the loss itself handed the recursion
            impala_loss(p, learn_apply(net), b, ImpalaConfig(**LOSS))
            return seen["vt"].log_rhos

        try:
            log_rhos = jax.jit(ratios)(params, batch)
        finally:
            vtrace.from_grouped_logits = real
    assert log_rhos.shape == (S * blocks, 1)
    assert float(jnp.max(jnp.abs(log_rhos))) < 1e-5


# ----------------------------------------- program against the reference

@pytest.mark.parametrize("S,boundaries", [(2, (2,)), (4, (1, 4))])
def test_loss_and_every_gradient_leaf_match_the_reference(S, boundaries):
    net, model = tiny(S)
    params = seeded(net, model, 7)
    batch = learn_batch(S, 5, 2, seed=3, boundaries=boundaries)
    spec = sdar_tiny.tiny(steps=S)
    with jax.default_matmul_precision("highest"):
        (logits, baseline), _ = jax.jit(net.apply)(
            params, batch["obs"], batch["done"], ())
        r_logits, r_values, _ = jax.jit(
            lambda p, b: reference.make_forward(spec)(
                p, b["obs"], b["done"], (), CAST)
        )(params, batch)
        np.testing.assert_allclose(logits, r_logits, atol=1e-5)
        np.testing.assert_allclose(baseline, r_values, atol=1e-5)
        (total, metrics), grads = jax.jit(jax.value_and_grad(
            lambda p, b: impala_loss(
                p, learn_apply(net), b, ImpalaConfig(**LOSS)),
            has_aux=True,
        ))(params, batch)
        (r_total, _), r_grads = jax.jit(jax.value_and_grad(
            lambda p, b: reference.make_loss(spec)(p, b, LOSS, CAST),
            has_aux=True,
        ))(params, batch)
    np.testing.assert_allclose(total, r_total, rtol=1e-5)
    for (path, g), r in zip(
        jax.tree_util.tree_flatten_with_path(grads)[0],
        jax.tree_util.tree_leaves(r_grads),
    ):
        scale = float(jnp.max(jnp.abs(r))) + 1e-8
        assert float(jnp.max(jnp.abs(g - r))) / scale < 2e-4, (
            jax.tree_util.keystr(path))
    # the step's own counters: rows, masked inputs, tokens, steps, pairs
    L, B = batch["obs"]["tokens"].shape
    assert int(metrics["blockdiff_rows"]) == (1 + S) * L * B
    assert int(metrics["blockdiff_scored_tokens"]) == (L - D) * B
    assert int(metrics["blockdiff_steps"]) == S * 5 * B
    rows = reference.block_diffusion_rows(
        L, 1 + S, jnp.cumsum(batch["done"][::S, 0].astype(jnp.int32)), D)
    pairs = int(jnp.sum(reference.seen_rows(rows, rows)))
    assert int(metrics["blockdiff_pairs"]) == 2 * B * pairs  # two layers


def test_three_rmsprop_steps_match_the_reference():
    """The step the benchmark times, through its first three updates,
    against the reference's loss, clip and RMSProp: the four numbers the
    cell's ``correct`` is decided by."""
    net, model = tiny(2)
    params = seeded(net, model, 7)
    batch = learn_batch(2, 7, 1, seed=9, boundaries=(3,))
    config = {"optimizer": OPTIMIZER, "loss": LOSS}
    optimizer = program.build_optimizer(config)
    step = make_impala_train_step(
        learn_apply(net), optimizer, ImpalaConfig(**LOSS), mesh=None,
        donate=False,
    )

    def recorded(state, batch):
        state, metrics = step(state, batch)
        return state, dict(metrics, mtp_loss=0.0)

    with jax.default_matmul_precision("highest"):
        _, first = reference_latent.program_first_steps(
            recorded, make_train_state(params, optimizer), batch, 3,
            OPTIMIZER["decay"],
        )
    follower = reference_latent.Follower(sdar_tiny.loss_fn, config)
    reference_side = follower.follow(
        lambda: jax.tree_util.tree_map(jnp.copy, params), batch, 3,
        against=first["grad_abs"])
    numbers = reference_latent.numbers(first, reference_side)
    assert max(numbers.values()) < 1e-4, numbers


# ------------------------------------- the flash path against the matrix

@pytest.mark.parametrize("S", [2, 4])
def test_the_copies_as_grouped_heads_on_the_flash_kernels(S):
    """``blockdiff_attention`` on the flash kernels (Pallas' interpreter),
    the copies as further grouped query heads under the rank rule and the
    rows' own blocks merged in, against ``dense_attention`` over all
    ``(1 + S) L`` rows with the visibility rule as a boolean matrix; and
    its gradients."""
    from jax.experimental.pallas import tpu as pltpu

    L, B, H, Hkv, hd, C = 256, 1, 4, 2, 16, 1 + S
    spec = lm.Diffusion(D, S, MASK)
    ks = jax.random.split(jax.random.PRNGKey(S), 4)
    q = jax.random.normal(ks[0], (C * L, B, H, hd), jnp.float32)
    k = jax.random.normal(ks[1], (C * L, B, Hkv, hd), jnp.float32)
    v = jax.random.normal(ks[2], (C * L, B, Hkv, hd), jnp.float32)
    w = jax.random.normal(ks[3], (C * L, B, H * hd), jnp.float32)
    done = np.zeros((S * (L // D - 1) + 1, B), bool)
    done[S * 21] = done[S * 40] = True
    ids = lm.blockdiff_ids(jnp.asarray(done), L, spec)
    rows = reference.block_diffusion_rows(
        L, C, jnp.cumsum(jnp.asarray(done[::S, 0]).astype(jnp.int32)), D)
    seen = reference.seen_rows(rows, rows)

    def program_side(q, k, v):
        o = lm.blockdiff_attention(
            q, k, v, ids, spec, backend="flash", block=128)
        return jnp.sum(o * w), o

    def matrix_side(q, k, v):
        # one segment id a row would not say "earlier blocks of the clean
        # copy": the matrix goes in as the scores' bias
        t = lambda x: x.transpose(1, 2, 0, 3)  # noqa: E731
        scores = jnp.einsum(
            "bhgqd,bhkd->bhgqk",
            t(q).reshape(B, Hkv, H // Hkv, C * L, hd), t(k)) * hd ** -0.5
        p = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        o = jnp.einsum("bhgqk,bhkd->bhgqd", p, t(v)).reshape(
            B, H, C * L, hd).transpose(2, 0, 1, 3).reshape(C * L, B, H * hd)
        return jnp.sum(o * w), o

    with jax.default_matmul_precision("highest"):
        # the model has no interpret switch: on the CPU the kernels run
        # under jax's own interpret context, forward and backward
        with pltpu.force_tpu_interpret_mode():
            (_, o), grads = jax.value_and_grad(
                program_side, argnums=(0, 1, 2), has_aux=True)(q, k, v)
        (_, r_o), r_grads = jax.jit(jax.value_and_grad(
            matrix_side, argnums=(0, 1, 2), has_aux=True))(q, k, v)
    np.testing.assert_allclose(o, r_o, atol=2e-5)
    for g, r in zip(grads, r_grads):
        np.testing.assert_allclose(g, r, atol=1e-4)
    # the same rule through the one attention call site, as a matrix of
    # group and rank for the earlier blocks
    earlier = attn_ops.dense_attention(
        q[:L].transpose(1, 2, 0, 3), k[:L].transpose(1, 2, 0, 3),
        v[:L].transpose(1, 2, 0, 3), segment_ids=ids, kv_segment_ids=ids,
        rank_bits=lm.blockdiff_bits(L, spec), return_lse=True,
    )[1]
    clean = np.asarray(seen)[:L, :L] & (
        np.arange(L)[None, :] // D < np.arange(L)[:, None] // D)
    assert np.array_equal(np.asarray(earlier[0, 0] > -1e29), clean.any(1))


# --------------------------------------------------- the episode boundary

def test_nothing_crosses_an_episode_boundary():
    """Other tokens, other reveal steps and other copies in episode 1
    leave every output of episode 2 bit for bit what it was."""
    S, blocks, first = 2, 6, 3
    net, model = tiny(S)
    params = seeded(net, model, 2)
    batch = learn_batch(S, blocks, 1, seed=4, boundaries=(first,))
    other = learn_batch(S, blocks, 1, seed=5, boundaries=(first,))
    mixed = {
        name: jnp.concatenate(
            [other["obs"][name][:D * first], batch["obs"][name][D * first:]])
        for name in ("tokens", "reveal_step")
    }
    apply = jax.jit(net.apply)
    (a_logits, a_values), _ = apply(params, batch["obs"], batch["done"], ())
    (b_logits, b_values), _ = apply(params, mixed, batch["done"], ())
    assert np.array_equal(a_logits[D * first:], b_logits[D * first:])
    assert np.array_equal(a_values[D * first:], b_values[D * first:])
    assert float(jnp.max(jnp.abs(a_logits[:D * first] - b_logits[:D * first]
                                 ))) > 1e-2
    # and without the boundary the second episode reads the first
    (c_logits, _), _ = apply(
        params, mixed, jnp.zeros_like(batch["done"]), ())
    (d_logits, _), _ = apply(
        params, batch["obs"], jnp.zeros_like(batch["done"]), ())
    assert float(jnp.max(jnp.abs(c_logits[D * first:] - d_logits[D * first:]
                                 ))) > 1e-3


def test_a_masked_copy_never_reads_its_own_blocks_clean_rows():
    """The classic leak: a token still masked in its scored copy may not
    move its own logits, nor those of its block's other tokens scored at
    the same step or before."""
    S, blocks = 2, 4
    net, model = tiny(S)
    params = seeded(net, model, 3)
    batch = learn_batch(S, blocks, 1, seed=6)
    reveal = np.asarray(batch["obs"]["reveal_step"][:, 0])
    i = D * 2 + int(np.argmax(reveal[D * 2:D * 3]))  # revealed at step 1
    tokens = batch["obs"]["tokens"].at[i, 0].add(1)
    apply = jax.jit(net.apply)
    (a, _), _ = apply(params, batch["obs"], batch["done"], ())
    (b, _), _ = apply(
        params, dict(batch["obs"], tokens=tokens), batch["done"], ())
    assert np.array_equal(a[:D * 3], b[:D * 3])  # its block and all before
    assert float(jnp.max(jnp.abs(a[D * 3:] - b[D * 3:]))) > 1e-4


def test_what_is_not_built_and_what_does_not_fit_says_so():
    batch = learn_batch(2, 3, 1, seed=0)
    for changes in (
        {"attention_kinds": {"blockdiff": {"window": 8, "rope": None}}},
        {"residual": "scaled"}, {"num_pred_heads": 2},
    ):
        net, _ = tiny(2, **changes)
        with pytest.raises(ValueError, match="block diffusion"):
            net.init(jax.random.PRNGKey(0), batch["obs"], batch["done"], ())
    net, _ = tiny(2)
    with pytest.raises(ValueError, match=r"done \(8, 1\)"):
        net.init(jax.random.PRNGKey(0), batch["obs"],
                 jnp.zeros((8, 1), bool), ())


# -------------------------------------------------- the configuration

def load(*parts):
    with open(os.path.join(REPO, "benchmark", *parts)) as f:
        return json.load(f)


def test_the_parameters_of_the_cut_are_counted():
    """ISSUE 49's table, by ``jax.eval_shape``: nothing is allocated."""
    config = load("configs", "sdar_share8.json")
    net = program.build_model(config)
    shapes = seeded_sdar.param_shapes(net)["params"]

    def count(tree):
        return sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(
            tree))

    block = shapes["block_0"]
    layers = block["norm1"]["scale"].shape[0]
    assert layers == 6
    assert count(block["attn"]) == layers * 18_874_624
    assert block["attn"]["q_norm"]["scale"].shape == (layers, 128)
    assert block["attn"]["k_norm"]["scale"].shape == (layers, 128)
    assert block["moe"]["router"].shape == (layers, 2048, 128)
    assert count(block["moe"]) == layers * (262_144 + 75_497_472)
    assert count(block) == layers * 94_638_336
    assert count(shapes["embed"]) + count(shapes["head"]) == 77_791_232
    assert count(shapes["final_norm"]) == 2048
    assert count(shapes["baseline"]) == 2049
    assert count(shapes) == 645_625_345


def test_the_configuration_is_the_published_model_cut_as_it_says():
    config = load("configs", "sdar_share8.json")
    cell = load("workloads", "sdar_learner_8k.json")
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        published = next(
            row for row in map(json.loads, f)
            if row["name"] == "SDAR-30B-A3B-Chat"
        )
    assert config["source"] == published["source_url"]
    assert sorted(config["reduced"]) == [
        "num_experts", "num_hidden_layers", "vocab_size"]
    for key, value in published["config"].items():
        if key in config["reduced"]:
            assert config["published"][key] == value
        else:
            assert config[key] == value, key
    model = config["model"]["kwargs"]
    assert (model["num_heads"], model["num_kv_heads"], model["head_dim"]) == (
        32, 4, 128)
    assert (model["num_experts"], model["top_k"]) == (128, 8)
    assert model["experts_held"] == [0, config["num_experts"]] == [0, 16]
    assert model["moe_intermediate_size"] == 768
    assert model["vocab_size"] == config["vocab_size"] == 151_936 // 8
    assert config["router_width"] == 128
    assert model["diffusion"] == {"block": 4, "steps": 2, "mask_id": 18725}
    assert 0 <= model["diffusion"]["mask_id"] < model["vocab_size"]
    assert len(config["assumed"]) >= 8
    # the traffic, to the letter: 8,192 tokens, blocks of 4, 2 steps
    steps, spec = cell["unroll_length"], config["observation"]
    assert steps == 4094 and cell["batch_per_chip"] == 1
    assert spec["block"] * (steps // spec["steps"] + 1) == 8192
    assert spec["first_step_reveals"] == {"1": 0.25, "2": 0.5, "3": 0.25}
    assert cell["done_rate"] == 1 / 2048 and cell["in_flight"] == 2
    # 2.5 x the mean load and two row tiles: a whole number of the grouped
    # product's tiles of 128 and no whole number of the row movers' tiles
    # of 512, so that gather and combine pass the buffer whole and the
    # step's time does not follow the routing
    mean = 3 * 8192 * 8 * 16 // 128
    rows = model["moe_buffer_rows"]
    assert rows == 61696 == int(2.5 * mean) + 256
    assert rows % 128 == 0 and rows % 512 != 0


def test_the_seeded_batch_keeps_to_the_contract():
    """``lib/seeded_sdar.py`` at the rehearsal's size: every (block, step)
    reveals a token, the first step 1 to 3 of four, no id is the mask's,
    a boundary lies at a block's first step alone and never at frame 0."""
    config = load("tests", "rehearsal_sdar", "benchmark", "configs",
                  "tiny_sdar.json")
    batch = jax.device_get(seeded_sdar.make_learn_batch(
        2147480011, config, 46, 2, 0.04))
    tokens, reveal = batch["obs"]["tokens"], batch["obs"]["reveal_step"]
    assert tokens.shape == reveal.shape == (96, 2)
    assert not (tokens == 31).any() and tokens.max() <= 30
    first = (reveal[:92].reshape(23, 4, 2) == 0).sum(axis=1)
    assert set(np.unique(first)) <= {1, 2, 3} and len(np.unique(first)) == 3
    assert (reveal[92:] == 2).all() and set(np.unique(reveal[:92])) == {0, 1}
    assert batch["done"].shape == batch["rewards"].shape == (47, 2)
    assert not batch["done"][1::2].any() and not batch["done"][0].any()
    assert batch["done"].any()
    assert np.array_equal(
        batch["action_step"],
        2 * (np.arange(92)[:, None] // 4) + reveal[:92])
    assert np.array_equal(batch["actions"], tokens[:92])
    assert batch["behavior_logits"].shape == (92, 2, 32)


def test_the_eight_shares_expert_parts_add_up_to_the_uncut_layer():
    """128 experts over 8 shares of 16, 8 a token: the partial sums the
    shares' expert layers give, added, are the reference's uncut layer."""
    d, E, f, top_k, rows = 32, 128, 8, 8, 48
    ks = jax.random.split(jax.random.PRNGKey(5), 5)

    def normal(key, shape, fan_in):
        return jax.random.normal(key, shape, jnp.float32) / np.sqrt(fan_in)

    moe = {"router": normal(ks[0], (d, E), d) * 4,
           "w_gate": normal(ks[1], (E, d, f), d),
           "w_up": normal(ks[2], (E, d, f), d),
           "w_down": normal(ks[3], (E, f, d), f)}
    z = jax.random.normal(ks[4], (rows, d), jnp.float32)
    parts = []
    for s in range(8):
        held = slice(16 * s, 16 * (s + 1))
        share = {name: moe[name][held]
                 for name in ("w_gate", "w_up", "w_down")}
        y, aux = moe_dropless(share, z, linear_scores(z, moe["router"]),
                              top_k=top_k, held=(16 * s, 16))
        parts.append(y)
    spec = dict(sdar_tiny.TINY, top_k=top_k, first_expert=0)
    whole = reference.experts(z, moe, spec, CAST)
    np.testing.assert_allclose(sum(parts), whole, rtol=2e-4, atol=2e-4)
    assert float(jnp.max(jnp.abs(parts[0] - whole))) > 1e-2
