"""libtpu lockfile serialization in the AOT prover.

libtpu holds ``/tmp/libtpu_lockfile`` for the holder's lifetime; a
SIGKILLed holder leaves it behind and every later init — including
deviceless compiles that need no chip — aborts. The helper
distinguishes a live sibling (flock held: wait within a TIME budget)
from a stale file (acquirable: unlink while holding the lock, inode-
checked) and passes through non-lockfile errors untouched.
"""

import fcntl
import os
import threading

import pytest

from dlrover_tpu.parallel import aot


class FakeTopologies:
    """Scripted get_topology_desc: fail N times, then succeed."""

    def __init__(self, failures, error):
        self.failures = failures
        self.error = error
        self.calls = 0

    def get_topology_desc(self, platform, topology_name):
        self.calls += 1
        if self.calls <= self.failures:
            raise RuntimeError(self.error)
        return f"topo:{topology_name}"


LOCK_ERR = ("ABORTED: Internal error when accessing libtpu "
            "multi-process lockfile.")


@pytest.fixture()
def lockfile(tmp_path, monkeypatch):
    path = str(tmp_path / "libtpu_lockfile")
    monkeypatch.setattr(aot, "_LIBTPU_LOCKFILE", path)
    return path


def test_non_lockfile_errors_pass_through(lockfile):
    fake = FakeTopologies(failures=99, error="some other compiler error")
    with pytest.raises(RuntimeError, match="other compiler"):
        aot._get_topology_desc_serialized(
            fake, "v5:2x2x4", wait_budget_s=1.0, poll_s=0.01,
        )
    assert fake.calls == 1  # no retry for unrelated failures


def test_stale_lockfile_is_removed_and_retried(lockfile):
    with open(lockfile, "w"):
        pass  # present, no holder: stale
    fake = FakeTopologies(failures=1, error=LOCK_ERR)
    out = aot._get_topology_desc_serialized(
        fake, "v5:2x2x4", wait_budget_s=5.0, poll_s=0.01,
    )
    assert out == "topo:v5:2x2x4"
    assert fake.calls == 2
    assert not os.path.exists(lockfile)  # the stale file was unlinked


def test_live_holder_is_waited_for_and_never_unlinked(lockfile):
    """While a sibling holds the flock the helper must wait and must
    NOT unlink the file; once the holder releases, the retry
    proceeds. The existence check runs INSIDE the holding window (the
    release callback, before unlocking), so a helper that wrongly
    unlinks under a live holder fails this test."""
    with open(lockfile, "w"):
        pass
    holder = open(lockfile)
    fcntl.flock(holder, fcntl.LOCK_EX)
    still_there_at_release = []
    released = threading.Event()

    class HeldTopologies:
        calls = 0

        def get_topology_desc(self, platform, topology_name):
            HeldTopologies.calls += 1
            if not released.is_set():
                # the sibling's init keeps failing while the lock is held
                raise RuntimeError(LOCK_ERR)
            return f"topo:{topology_name}"

    def release():
        # sampled while the hold is still in effect
        still_there_at_release.append(os.path.exists(lockfile))
        fcntl.flock(holder, fcntl.LOCK_UN)
        holder.close()
        released.set()

    timer = threading.Timer(0.4, release)
    timer.start()
    try:
        out = aot._get_topology_desc_serialized(
            HeldTopologies(), "v5:2x2x4", wait_budget_s=10.0,
            poll_s=0.1,
        )
        assert out == "topo:v5:2x2x4"
        assert HeldTopologies.calls >= 2
        assert still_there_at_release == [True], (
            "the lockfile was unlinked while a live holder held it"
        )
    finally:
        timer.cancel()


def test_gives_up_when_budget_exhausted(lockfile):
    with open(lockfile, "w"):
        pass
    fake = FakeTopologies(failures=99, error=LOCK_ERR)
    with pytest.raises(RuntimeError, match="lockfile"):
        aot._get_topology_desc_serialized(
            fake, "v5:2x2x4", wait_budget_s=0.3, poll_s=0.01,
        )
    assert fake.calls >= 2  # it did retry within the budget
