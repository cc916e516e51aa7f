"""Checkpoint manager: async writes, atomic layout, restore, retention,
and elastic restore (save on 1 device → restore onto an 8-device mesh,
via subprocess so the device count doesn't leak into this process)."""
from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint.manager import CheckpointManager, load_pytree, save_pytree


def tree():
    return {
        "layers": {"w": jnp.arange(12, dtype=jnp.float32).reshape(3, 4)},
        "step_scalar": jnp.float32(3.5),
        "embed": {"table": jnp.ones((16, 8), jnp.bfloat16)},
    }


def test_pytree_roundtrip(tmp_path):
    t = tree()
    save_pytree(t, tmp_path / "x")
    like = jax.tree_util.tree_map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), t)
    r = load_pytree(tmp_path / "x", like)
    for a, b in zip(jax.tree_util.tree_leaves(t), jax.tree_util.tree_leaves(r)):
        np.testing.assert_array_equal(np.asarray(a, np.float32), np.asarray(b, np.float32))


def test_save_restore_async(tmp_path):
    with CheckpointManager(tmp_path, keep_last=2) as mgr:
        params = tree()
        state = {"opt": jnp.zeros((4,))}
        mgr.save(3, params, state)
        mgr.wait()
        assert mgr.latest_step() == 3
        p2, s2 = mgr.restore(3, params, state)
        np.testing.assert_array_equal(np.asarray(p2["layers"]["w"]),
                                      np.asarray(params["layers"]["w"]))


def test_retention_and_latest(tmp_path):
    with CheckpointManager(tmp_path, keep_last=2) as mgr:
        for s in (1, 2, 3, 4):
            mgr.save(s, {"w": jnp.full((2,), float(s))}, blocking=True)
        assert mgr.latest_step() == 4
        kept = sorted(p.name for p in Path(tmp_path).glob("step_*"))
        assert len(kept) == 2 and kept[-1].endswith("0004")


def test_no_partial_checkpoint_visible(tmp_path):
    """Atomicity: while a write is in flight, LATEST still points at the
    previous complete checkpoint."""
    mgr = CheckpointManager(tmp_path, keep_last=3)
    mgr.save(1, {"w": jnp.zeros((2,))}, blocking=True)
    big = {"w": jnp.zeros((512, 512))}
    mgr.save(2, big)  # async
    step = mgr.latest_step()
    assert step in (1, 2)  # never a corrupt intermediate
    mgr.wait()
    assert mgr.latest_step() == 2
    mgr.close()


def test_restart_resumes_training(tmp_path):
    """Train → checkpoint → 'crash' → restore → identical continuation."""
    import dataclasses

    from repro.models.registry import get_arch
    from repro.train.optimizer import AdamWConfig
    from repro.train.step import TrainStepConfig, make_train_step
    from repro.data.pipeline import SyntheticLMStream

    arch = get_arch("olmo-1b")
    arch = dataclasses.replace(arch, cfg=arch.cfg.reduced())
    key = jax.random.PRNGKey(0)
    stream = SyntheticLMStream(arch.cfg.vocab_size, 16, 4)
    init_state, step = make_train_step(arch, AdamWConfig(lr=1e-3),
                                       TrainStepConfig(donate=False))
    params = arch.init(key)
    state = init_state(params)

    # run 5 steps, checkpoint at step 3
    mgr = CheckpointManager(tmp_path)
    for i in range(5):
        params, state, _ = step(params, state, stream.batch_at(i))
        if i == 2:
            mgr.save(3, params, state, blocking=True)
    final_direct = params

    # 'crash'; restore and continue from step 3 with the same stream offsets
    p2, s2 = mgr.restore(3, params, state)
    for i in range(3, 5):
        p2, s2, _ = step(p2, s2, stream.batch_at(i))
    for a, b in zip(jax.tree_util.tree_leaves(final_direct),
                    jax.tree_util.tree_leaves(p2)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), rtol=1e-6, atol=1e-6)
    mgr.close()


ELASTIC_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys, json
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.checkpoint.manager import CheckpointManager

root = sys.argv[1]
mgr = CheckpointManager(root)
like = {"w": jax.ShapeDtypeStruct((16, 8), jnp.float32)}
step, params, _ = mgr.restore_latest(like)
mesh = jax.make_mesh((4, 2), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
sharded = jax.device_put(params["w"], NamedSharding(mesh, P("data", "model")))
assert len(sharded.addressable_shards) == 8
total = float(jnp.sum(sharded))
print(json.dumps({"step": step, "sum": total, "shards": len(sharded.addressable_shards)}))
"""


def test_elastic_restore_onto_8_devices(tmp_path):
    w = jnp.arange(128, dtype=jnp.float32).reshape(16, 8)
    with CheckpointManager(tmp_path) as mgr:
        mgr.save(7, {"w": w}, blocking=True)
    env = dict(os.environ, PYTHONPATH="src")
    out = subprocess.run(
        [sys.executable, "-c", ELASTIC_SCRIPT, str(tmp_path)],
        capture_output=True, text=True, env=env, cwd=Path(__file__).parents[1],
        timeout=180,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["step"] == 7 and res["shards"] == 8
    assert abs(res["sum"] - float(jnp.sum(w))) < 1e-3
