import json
import os
import numpy as np
import pytest

from repro.api.checkpoint import CheckpointManager


def _payload(seed=0):
    rng = np.random.default_rng(seed)
    return {"params": {"w": rng.normal(size=(4, 3)).astype(np.float32),
                       "b": rng.normal(size=(3,)).astype(np.float32)},
            "opt": [rng.normal(size=(2,)), rng.normal(size=(2,))],
            "step": np.asarray(7)}


def test_roundtrip(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    p = _payload()
    mgr.save(3, p)
    got, step = mgr.restore_latest()
    assert step == 3
    np.testing.assert_array_equal(got["params"]["w"], p["params"]["w"])
    assert isinstance(got["opt"], list) and len(got["opt"]) == 2
    np.testing.assert_array_equal(got["opt"][1], p["opt"][1])


def test_retention(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2)
    for s in range(5):
        mgr.save(s, _payload(s))
    assert mgr.steps() == [3, 4]


def test_corruption_fallback(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=5)
    mgr.save(1, _payload(1))
    mgr.save(2, _payload(2))
    # corrupt latest
    d = mgr._step_dir(2)
    victim = next(f for f in os.listdir(d) if f.endswith(".npy"))
    with open(os.path.join(d, victim), "wb") as f:
        f.write(b"garbage")
    got, step = mgr.restore_latest()
    assert step == 1  # fell back past the corrupted checkpoint
    np.testing.assert_array_equal(got["params"]["w"], _payload(1)["params"]["w"])


def test_partial_write_ignored(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(1, _payload(1))
    # simulate a crash mid-save: tmp dir left behind, no manifest rename
    os.makedirs(os.path.join(str(tmp_path), "step_0000000009.tmp"))
    got, step = mgr.restore_latest()
    assert step == 1


def test_async_save(tmp_path):
    mgr = CheckpointManager(str(tmp_path), async_save=True)
    mgr.save(1, _payload(1), block=False)
    mgr.wait()
    assert mgr.steps() == [1]
    got, _ = mgr.restore_latest()
    np.testing.assert_array_equal(got["params"]["b"], _payload(1)["params"]["b"])


def test_async_handoff_semantics(tmp_path):
    """block=False on an async manager hands the save to a background
    thread; every other combination runs synchronously on the caller."""
    mgr = CheckpointManager(str(tmp_path), async_save=True)
    mgr.save(1, _payload(1), block=False)
    assert mgr._thread is not None          # handed off, not inline
    mgr.wait()
    assert mgr._thread is None
    mgr.save(2, _payload(2), block=True)    # block=True: sync even when
    assert mgr._thread is None              # async_save=True
    sync = CheckpointManager(str(tmp_path), async_save=False)
    sync.save(3, _payload(3), block=False)  # async_save=False: always sync
    assert sync._thread is None
    assert mgr.steps() == [1, 2, 3]


def test_async_caller_mutation_safe(tmp_path):
    """The async hand-off copies the payload before returning, so caller
    mutation right after save(block=False) cannot tear the checkpoint."""
    mgr = CheckpointManager(str(tmp_path), async_save=True)
    p = _payload(4)
    mgr.save(1, p, block=False)
    p["params"]["w"][:] = -1.0
    mgr.wait()
    got, _ = mgr.restore_latest()
    np.testing.assert_array_equal(got["params"]["w"],
                                  _payload(4)["params"]["w"])


def test_async_save_error_surfaces_in_wait(tmp_path):
    """An exception inside the save thread re-raises from wait() (or from
    the next save(), which waits first) instead of vanishing — and is
    raised exactly once."""
    mgr = CheckpointManager(str(tmp_path), async_save=True)
    # a FILE where the temp DIR must go: os.makedirs/shutil.rmtree fails
    with open(os.path.join(str(tmp_path), "step_0000000005.tmp"), "w"):
        pass
    mgr.save(5, _payload(5), block=False)
    with pytest.raises(OSError):
        mgr.wait()
    mgr.wait()  # cleared: does not re-raise
    assert mgr.steps() == []
    # the same failure surfaces from the next save() when wait() is skipped
    with open(os.path.join(str(tmp_path), "step_0000000006.tmp"), "w"):
        pass
    mgr.save(6, _payload(6), block=False)
    with pytest.raises(OSError):
        mgr.save(7, _payload(7), block=False)
    mgr.wait()


def test_manifest_integrity_recorded(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(4, _payload())
    man = json.load(open(os.path.join(mgr._step_dir(4), "manifest.json")))
    assert man["step"] == 4
    assert all("sha256" in v for v in man["arrays"].values())
