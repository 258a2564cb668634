"""Multi-process jax.distributed bring-up on CPU: 2 controllers, one global
mesh, one dp-sharded train step fed via host_local_batch_to_global
(the multi-host tier of the two-tier comm design; no TPU required)."""

import os
import socket
import subprocess
import sys
import textwrap

import pytest

_WORKER = textwrap.dedent(
    """
    import os, sys
    sys.path.insert(0, os.environ["REPO"])
    import jax
    jax.config.update("jax_platforms", "cpu")

    rank = int(sys.argv[1])
    nproc = int(sys.argv[2])
    coord = sys.argv[3]

    from moolib_tpu.parallel import distributed as dist

    dist.initialize(coordinator_address=coord, num_processes=nproc,
                    process_id=rank)
    assert dist.process_count() == nproc
    assert jax.local_device_count() == 2
    assert jax.device_count() == 2 * nproc

    import numpy as np
    import jax.numpy as jnp
    import optax
    from moolib_tpu.learner import (
        ImpalaConfig, make_impala_train_step, make_train_state,
        replicate_state,
    )
    from moolib_tpu.models import ImpalaNet

    mesh = dist.global_mesh(dp=2 * nproc)
    net = ImpalaNet(num_actions=4, channels=(4,))
    T, B_local, H, W, C = 2, 2, 8, 8, 1
    rng = np.random.default_rng(rank)
    local = {
        "obs": rng.integers(0, 255, (T + 1, B_local, H, W, C), dtype=np.uint8),
        "done": rng.random((T + 1, B_local)) < 0.1,
        "rewards": rng.standard_normal((T + 1, B_local)).astype(np.float32),
        "actions": rng.integers(0, 4, (T, B_local)).astype(np.int32),
        "behavior_logits": np.zeros((T, B_local, 4), np.float32),
        "core_state": (),
    }
    batch = dist.host_local_batch_to_global(mesh, local)
    assert batch["obs"].shape == (T + 1, B_local * nproc, H, W, C)

    # Same init on every controller (same seed), replicated over the mesh.
    params = net.init(
        jax.random.PRNGKey(0),
        jnp.zeros((T + 1, 1, H, W, C), jnp.uint8),
        jnp.zeros((T + 1, 1), bool), (),
    )
    opt = optax.adam(1e-3)
    state = replicate_state(make_train_state(params, opt), mesh)
    step = make_impala_train_step(
        net.apply, opt, ImpalaConfig(), mesh=mesh, donate=False
    )
    state, metrics = step(state, batch)
    loss = float(metrics["total_loss"])
    assert np.isfinite(loss), loss
    fp = float(sum(jnp.sum(jnp.abs(l.astype(jnp.float32)))
                   for l in jax.tree_util.tree_leaves(state.params)))
    print(f"RESULT rank={rank} loss={loss:.6f} fp={fp:.6f}", flush=True)
    """
)


@pytest.mark.integration
def test_two_process_distributed_train_step(tmp_path):
    worker = tmp_path / "dist_worker.py"
    worker.write_text(_WORKER)
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        coord = f"127.0.0.1:{s.getsockname()[1]}"

    env = dict(os.environ)
    env["REPO"] = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    # On jax builds that support CPU collectives (the skipif gate above),
    # select the gloo transport explicitly — the default is process-local.
    env.setdefault("JAX_CPU_COLLECTIVES_IMPLEMENTATION", "gloo")
    procs = [
        subprocess.Popen(
            [sys.executable, str(worker), str(rank), "2", coord],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True,
        )
        for rank in range(2)
    ]
    outs = []
    for p in procs:
        out, _ = p.communicate(timeout=300)
        outs.append(out)
        assert p.returncode == 0, out[-3000:]
    results = {}
    for out in outs:
        line = [l for l in out.splitlines() if l.startswith("RESULT")][0]
        kv = dict(p.split("=") for p in line.split()[1:])
        results[kv["rank"]] = (kv["loss"], kv["fp"])
    # Both controllers computed the SAME global step: identical loss and
    # updated-parameter fingerprint.
    assert results["0"] == results["1"], results
