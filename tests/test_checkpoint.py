"""Fault-tolerance tests: atomic checkpointing, resume, elastic restore,
and sha256 integrity verification (DESIGN.md §9)."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import CheckpointCorruptError, CheckpointManager
from repro.launch.mesh import auto_mesh
from repro.data import DataConfig, SyntheticStream, make_batch
from repro.distributed import steps
from repro.distributed.sharding import make_rules
from repro.models import ModelConfig
from repro.models.base import init_params
from repro.optim import AdamWConfig

RULES = make_rules()
CFG = ModelConfig(family="dense", n_layers=2, d_model=32, n_heads=2,
                  n_kv_heads=1, d_ff=64, vocab=64, attn_impl="ref",
                  remat=False)
OPT = AdamWConfig(lr=1e-3, warmup_steps=2, decay_steps=50)


def _train(state, step_fn, stream, n):
    for _ in range(n):
        batch = jax.tree.map(jnp.asarray, next(stream))
        state, m = step_fn(state, batch)
    return state, m


def test_save_restore_roundtrip(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep_last=2)
    state = init_params(steps.train_state_decl(CFG, OPT),
                        jax.random.PRNGKey(0), jnp.float32)
    mgr.save(7, state, meta={"data_state": {"seed": 1, "step": 7}})
    restored, manifest = mgr.restore(state)
    assert manifest["step"] == 7
    assert manifest["data_state"]["step"] == 7
    for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_atomic_publish_and_gc(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep_last=2)
    state = {"w": jnp.arange(4.0)}
    for s in (1, 2, 3, 4):
        mgr.save(s, state)
    assert mgr.all_steps() == [3, 4]
    # a stale .tmp dir (simulated crash) is ignored by restore
    os.makedirs(os.path.join(str(tmp_path), "step_00000009.tmp"))
    assert mgr.latest_step() == 4


def test_crash_resume_training_is_exact(tmp_path):
    """Train 6 steps; 'crash' after 3; resume from the checkpoint and data
    state -> final params identical to the uninterrupted run."""
    dc = DataConfig(batch=4, seq=16, vocab=64, task="copy", seed=5)
    step_fn = jax.jit(steps.make_train_step(CFG, OPT, RULES))

    # uninterrupted
    s_full = init_params(steps.train_state_decl(CFG, OPT),
                         jax.random.PRNGKey(0), jnp.float32)
    s_full, _ = _train(s_full, step_fn, SyntheticStream(dc), 6)

    # interrupted at step 3
    mgr = CheckpointManager(str(tmp_path))
    s_a = init_params(steps.train_state_decl(CFG, OPT),
                      jax.random.PRNGKey(0), jnp.float32)
    stream = SyntheticStream(dc)
    s_a, _ = _train(s_a, step_fn, stream, 3)
    mgr.save(3, s_a, meta={"data_state": stream.state()})
    del s_a                                 # crash

    template = init_params(steps.train_state_decl(CFG, OPT),
                           jax.random.PRNGKey(99), jnp.float32)
    s_b, manifest = mgr.restore(template)
    stream_b = SyntheticStream.from_state(dc, manifest["data_state"])
    s_b = jax.tree.map(jnp.asarray, s_b)
    s_b, _ = _train(s_b, step_fn, stream_b, 3)

    for a, b in zip(jax.tree.leaves(s_full["params"]),
                    jax.tree.leaves(s_b["params"])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5)


# ---------------------------------------------------------------------------
# Integrity: sha256 sidecar verification (DESIGN.md §9)
# ---------------------------------------------------------------------------

def _step_file(tmp_path, step, name):
    return os.path.join(str(tmp_path), f"step_{step:08d}", name)


def test_save_writes_sha256_sidecar(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(1, {"w": jnp.arange(8.0)})
    with open(_step_file(tmp_path, 1, "sha256.json")) as f:
        digests = json.load(f)
    assert set(digests) == {"arrays.npz", "manifest.json"}
    assert all(len(d) == 64 for d in digests.values())
    # verified restore round-trips
    restored, _ = mgr.restore({"w": jnp.arange(8.0)})
    np.testing.assert_array_equal(np.asarray(restored["w"]),
                                  np.arange(8.0))


def test_bitflip_raises_checkpoint_corrupt_error(tmp_path):
    from repro.testing import faults
    mgr = CheckpointManager(str(tmp_path))
    template = {"w": jnp.arange(64.0)}
    mgr.save(1, template)
    faults.flip_byte(_step_file(tmp_path, 1, "arrays.npz"))
    with pytest.raises(CheckpointCorruptError, match="sha256 mismatch"):
        mgr.restore(template)
    # the escape hatch skips verification (salvage path): whether the
    # load then succeeds depends on where the flip landed, but it must
    # not be an integrity error
    try:
        mgr.restore(template, verify=False)
    except CheckpointCorruptError:                # pragma: no cover
        pytest.fail("verify=False must skip the integrity check")
    except Exception:
        pass                                      # npz CRC may still balk


def test_truncation_raises_checkpoint_corrupt_error(tmp_path):
    from repro.testing import faults
    mgr = CheckpointManager(str(tmp_path))
    template = {"w": jnp.arange(64.0), "b": jnp.ones((16, 16))}
    mgr.save(3, template)
    faults.truncate_file(_step_file(tmp_path, 3, "arrays.npz"), 0.5)
    with pytest.raises(CheckpointCorruptError, match="sha256 mismatch"):
        mgr.restore(template)


def test_manifest_tamper_is_detected(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(1, {"w": jnp.arange(4.0)}, meta={"lr": 1e-3})
    mpath = _step_file(tmp_path, 1, "manifest.json")
    with open(mpath) as f:
        manifest = json.load(f)
    manifest["lr"] = 99.0                         # hand edit
    with open(mpath, "w") as f:
        json.dump(manifest, f)
    with pytest.raises(CheckpointCorruptError, match="manifest.json"):
        mgr.restore({"w": jnp.arange(4.0)})
    # verify=False restores the tampered (but loadable) checkpoint
    _, got = mgr.restore({"w": jnp.arange(4.0)}, verify=False)
    assert got["lr"] == 99.0


def test_legacy_checkpoint_without_sidecar_warns_and_restores(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(2, {"w": jnp.arange(4.0)})
    os.remove(_step_file(tmp_path, 2, "sha256.json"))  # pre-sidecar era
    with pytest.warns(RuntimeWarning, match="unverified"):
        restored, _ = mgr.restore({"w": jnp.arange(4.0)})
    np.testing.assert_array_equal(np.asarray(restored["w"]),
                                  np.arange(4.0))


def test_crash_before_publish_keeps_previous_step_restorable(tmp_path):
    """A crash between the temp write and the atomic rename leaves the
    previous published step as the (verified) latest."""
    from repro.testing import faults
    from repro.testing.faults import InjectedCrash
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(1, {"w": jnp.zeros(4)})
    with faults.crash_before_publish("checkpoint"):
        with pytest.raises(InjectedCrash):
            mgr.save(2, {"w": jnp.ones(4)})
    assert mgr.latest_step() == 1                 # step 2 never published
    restored, manifest = mgr.restore({"w": jnp.zeros(4)})  # verified
    assert manifest["step"] == 1
    np.testing.assert_array_equal(np.asarray(restored["w"]), np.zeros(4))
    # the interrupted save retries cleanly once the fault is gone
    mgr.save(2, {"w": jnp.ones(4)})
    assert mgr.latest_step() == 2


def test_elastic_restore_new_mesh(tmp_path):
    """A checkpoint written under one mesh restores onto a different mesh
    shape (elastic restart): arrays are placed with the new shardings."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    mgr = CheckpointManager(str(tmp_path))
    state = {"w": jnp.arange(16.0).reshape(4, 4)}
    mgr.save(1, state, meta={"mesh": [1, 1]})

    mesh = auto_mesh((1, 1), ("data", "model"))
    shardings = {"w": NamedSharding(mesh, P(None, "model"))}
    restored, _ = mgr.restore(state, shardings=shardings)
    np.testing.assert_array_equal(np.asarray(restored["w"]),
                                  np.asarray(state["w"]))
    assert restored["w"].sharding.is_equivalent_to(shardings["w"], 2)
