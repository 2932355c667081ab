"""One process of a two-process job whose devices disagree on room for
a snapshot (tests/test_ckpt_snapshot.py): every process has to take
the same path through a save.

argv: coordinator port, process id, checkpoint directory. The first
save finds process 1 without room, the second finds room everywhere.
Prints one JSON line: the ``mode`` of each of its ``ckpt_save`` events
and the newest committed step.
"""

import json
import os
import sys

import jax

port, pid, directory = int(sys.argv[1]), int(sys.argv[2]), sys.argv[3]
jax.distributed.initialize(f"localhost:{port}", num_processes=2,
                           process_id=pid)

import numpy as np  # noqa: E402
from jax.sharding import Mesh, NamedSharding, PartitionSpec  # noqa: E402

import dlrover_tpu.checkpoint.manager as manager_module  # noqa: E402
from dlrover_tpu.checkpoint import (  # noqa: E402
    ElasticCheckpointManager,
    abstract_like,
)
from dlrover_tpu.telemetry.events import read_events  # noqa: E402


class Device:
    def __init__(self, free):
        self.free = free

    def memory_stats(self):
        return {"bytes_limit": 100, "bytes_in_use": 100 - self.free}


sharding = NamedSharding(Mesh(np.array(jax.devices()), ("data",)),
                         PartitionSpec("data"))
rows = jax.local_device_count()
state = {"w": jax.make_array_from_process_local_data(
    sharding, np.full((rows, 4), pid, np.float32))}
mgr = ElasticCheckpointManager(directory, staging_dir="")
for step, free_on_process_1 in ((1, 10), (2, 90)):
    free = free_on_process_1 if pid == 1 else 90
    manager_module._bytes_by_device = lambda tree: {Device(free): 50}
    assert mgr.save(step, state, force=True)
mgr.wait()
back = mgr.restore(abstract_like(state, {"w": sharding}))
local = np.concatenate(
    [np.asarray(s.data) for s in back["state"]["w"].addressable_shards])
assert back["step"] == 2 and (local == pid).all(), (back["step"], local)
mgr.close()
print(json.dumps({
    "process": pid,
    "modes": [e["mode"] for e in read_events(
        os.environ["DLROVER_TPU_EVENTS_FILE"]) if e["kind"] == "ckpt_save"],
    "latest": back["step"]}), flush=True)
