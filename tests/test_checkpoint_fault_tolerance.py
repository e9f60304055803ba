import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import Checkpointer, latest_step, restore, save
from repro.distributed.fault_tolerance import (
    FaultPolicy,
    SimulatedTransientFailure,
    TrainLoop,
)
from repro.launch.mesh import make_mesh


def _state(v=0.0):
    return {"params": {"w": jnp.full((4, 4), v)}, "step": jnp.int32(v)}


def test_save_restore_roundtrip(tmp_path):
    s = _state(3.0)
    save(tmp_path, 7, s)
    assert latest_step(tmp_path) == 7
    template = jax.eval_shape(lambda: _state())
    r = restore(tmp_path, 7, template)
    np.testing.assert_array_equal(np.asarray(r["params"]["w"]), 3.0)
    assert int(r["step"]) == 3


def test_atomic_commit_ignores_uncommitted(tmp_path):
    save(tmp_path, 1, _state(1.0))
    # simulate a crash: uncommitted dir with a bigger step number
    bad = tmp_path / "step_00000002"
    bad.mkdir()
    (bad / "manifest.json").write_text("{}")
    assert latest_step(tmp_path) == 1  # COMMITTED marker missing -> ignored


def test_checksum_verification(tmp_path):
    save(tmp_path, 1, _state(1.0))
    # corrupt a leaf
    leaf = next((tmp_path / "step_00000001").glob("*.npy"))
    arr = np.load(leaf)
    arr = arr + 1
    np.save(leaf, arr)
    with pytest.raises(IOError):
        restore(tmp_path, 1, jax.eval_shape(lambda: _state()))


def test_retention(tmp_path):
    ck = Checkpointer(tmp_path, keep=2)
    for s in (1, 2, 3, 4):
        ck.save(s, _state(float(s)))
    ck.wait()
    assert latest_step(tmp_path) == 4
    assert not (tmp_path / "step_00000001").exists()
    assert (tmp_path / "step_00000003").exists()


def test_async_save(tmp_path):
    ck = Checkpointer(tmp_path, async_=True)
    ck.save(5, _state(5.0))
    ck.wait()
    assert latest_step(tmp_path) == 5


def _toy_train_step(state, batch):
    w = state["params"]["w"] + batch["x"].sum()
    return ({"params": {"w": w}, "step": state["step"] + 1},
            {"loss": jnp.sum(w)})


def _data():
    i = 0
    while True:
        yield {"x": jnp.full((2,), 0.5)}
        i += 1


def test_trainloop_checkpoint_restart_bitwise(tmp_path):
    """Kill mid-run, restart, final state must equal an uninterrupted run."""
    policy = FaultPolicy(checkpoint_every=5)

    # uninterrupted reference
    ck0 = Checkpointer(tmp_path / "ref")
    loop0 = TrainLoop(_toy_train_step, ck0, policy)
    ref_state, _ = loop0.run(_state(0.0), _data(), 12)

    # crash at step 8 (after the step-5 checkpoint)
    ck1 = Checkpointer(tmp_path / "crash")
    crashes = {"armed": True}

    def bomb(step):
        if step == 8 and crashes["armed"]:
            crashes["armed"] = False
            raise KeyboardInterrupt  # hard kill, not a retryable fault

    loop1 = TrainLoop(_toy_train_step, ck1, policy, fault_hook=bomb)
    with pytest.raises(KeyboardInterrupt):
        loop1.run(_state(0.0), _data(), 12)

    # restart: resume from checkpoint 5, replay the data stream from there.
    # the toy stream is stateless-per-step so skipping consumed batches is a
    # no-op; real pipelines restore their cursor from the step number.
    loop2 = TrainLoop(_toy_train_step, Checkpointer(tmp_path / "crash"),
                      policy)
    state, start = loop2.resume_or_init(lambda: _state(0.0))
    assert start == 5
    final, _ = loop2.run(state, _data(), 12, start_step=start)
    np.testing.assert_array_equal(np.asarray(final["params"]["w"]),
                                  np.asarray(ref_state["params"]["w"]))


def test_trainloop_retries_transient(tmp_path):
    attempts = {"n": 0}

    def flaky(step):
        if step == 3 and attempts["n"] < 2:
            attempts["n"] += 1
            raise SimulatedTransientFailure("link flap")

    loop = TrainLoop(_toy_train_step, Checkpointer(tmp_path),
                     FaultPolicy(max_retries_per_step=3), fault_hook=flaky)
    _, end = loop.run(_state(0.0), _data(), 6)
    assert end == 6
    rec = [r for r in loop.records if r.step == 3][0]
    assert rec.retries == 2


def test_trainloop_straggler_detection(tmp_path):
    import time

    def slow_step(state, batch):
        if int(state["step"]) == 4:
            time.sleep(0.25)
        else:
            time.sleep(0.01)
        return _toy_train_step(state, batch)

    loop = TrainLoop(slow_step, Checkpointer(tmp_path),
                     FaultPolicy(straggler_factor=5.0))
    loop.run(_state(0.0), _data(), 8)
    assert 4 in loop.straggler_events


def test_elastic_restore_different_sharding(tmp_path):
    """Checkpoint written under one mesh restores onto another (resharding
    happens at load — elastic scaling)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    s = {"w": jnp.arange(16.0).reshape(4, 4)}
    save(tmp_path, 1, s)
    mesh = make_mesh((1,), ("data",))
    shard = {"w": NamedSharding(mesh, P("data", None))}
    r = restore(tmp_path, 1, jax.eval_shape(lambda: s), shardings=shard)
    np.testing.assert_array_equal(np.asarray(r["w"]), np.asarray(s["w"]))
    assert r["w"].sharding == shard["w"]
