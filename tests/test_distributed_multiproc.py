"""Simulated multi-host cluster: two real OS processes + a coordinator.

The reference validates multi-machine behavior only in an external network
emulator (SURVEY.md §4); here two local processes form an actual
``jax.distributed`` cluster over localhost (CPU backend, 4 virtual devices
per process) and assert the things ``tests/test_distributed.py`` can only
assert vacuously on one process:

* ``initialize`` with explicit coordinator args forms the cluster
  (process_count == 2, 8 global devices);
* ``multihost_pipeline_mesh`` spans both hosts and lays the stage axis out
  host-major — consecutive stages stay on one host except at the single
  host-boundary hop (the DCN-crossing claim of
  parallel/distributed.py:60-74);
* a ``psum`` over the global mesh actually crosses the process boundary.
"""

import os
import subprocess
import sys

import pytest

_WORKER = r"""
import os, sys
import numpy as np

import jax
from defer_tpu.parallel.distributed import (initialize,
                                            multihost_pipeline_mesh,
                                            process_local_batch)
from defer_tpu.parallel.mesh import STAGE_AXIS

pid = int(sys.argv[1])
initialize(coordinator_address="127.0.0.1:%PORT%",
           num_processes=2, process_id=pid)

assert jax.process_count() == 2, jax.process_count()
devs = jax.devices()
assert len(devs) == 8, len(devs)

mesh = multihost_pipeline_mesh(8)
stage_devs = list(mesh.devices.flatten())
# host-major stage layout: stages 0-3 on process 0, stages 4-7 on
# process 1 -> exactly ONE host-boundary hop in the stage chain
owners = [d.process_index for d in stage_devs]
assert owners == sorted(owners), owners
assert sum(1 for a, b in zip(owners, owners[1:]) if a != b) == 1, owners

# a collective over the global mesh crosses the process boundary
from jax.sharding import NamedSharding, PartitionSpec as P
x = jax.device_put(
    np.arange(8, dtype=np.float32),
    NamedSharding(mesh, P(STAGE_AXIS)))
total = jax.jit(
    jax.shard_map(lambda a: jax.lax.psum(a, STAGE_AXIS), mesh=mesh,
                  in_specs=P(STAGE_AXIS), out_specs=P()))(x)
np.testing.assert_allclose(np.asarray(total), [28.0])

assert process_local_batch(16) == 8
print(f"worker {pid} OK", flush=True)
"""


@pytest.mark.slow
def test_two_process_cluster(tmp_path):
    import socket
    srv = socket.create_server(("127.0.0.1", 0))
    port = srv.getsockname()[1]
    srv.close()

    script = tmp_path / "worker.py"
    script.write_text(_WORKER.replace("%PORT%", str(port)))

    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
        "PYTHONPATH": os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))),
    })
    procs = [subprocess.Popen([sys.executable, str(script), str(i)],
                              env=env, stdout=subprocess.PIPE,
                              stderr=subprocess.PIPE, text=True)
             for i in range(2)]
    outs = []
    for p in procs:
        try:
            out, err = p.communicate(timeout=300)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            raise
        outs.append((p.returncode, out, err))
    for i, (rc, out, err) in enumerate(outs):
        assert rc == 0, f"worker {i} rc={rc}\n{err[-3000:]}"
        assert f"worker {i} OK" in out
