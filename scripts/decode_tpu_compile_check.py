"""The decode program under the TPU's own compiler, at a size that shows
the cliff (docs/DECODE_CLIFF.md) — no chip needed, not part of the tests.

The compiler for a *described* v5e (``jax.experimental.topologies``) runs
on this host.  r05's cliff row (gpt 12L/768, 64 sequences, 512 positions:
1.1 GiB of bf16 cache) took 558 ms a step on the chip while the ring kept
its blocks' caches in one stacked array: XLA:TPU wrapped every cache
write in a ``remat_uncompressed``/``remat_compressed`` pair of copies of
the whole stack, which shows in the compiled text (214 of them at the
batch cell's size on PR 24's tree).  With per-block buffers there is none.  Run it before
spending chip time on a change to how the ring holds its caches:

    env JAX_PLATFORMS=cpu python scripts/decode_tpu_compile_check.py

~30 s; exit 0 and one JSON line when no such copy is in the compiled
text, 1 when there is.  A process of its own on purpose: loading the
TPU's library takes a machine-wide lock (``/tmp/libtpu_lockfile``) that
is held until the process ends, so this must not live in a long test run.
"""

import json
import os
import re
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
from jax.experimental import topologies
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from defer_tpu.models import gpt
from defer_tpu.parallel.mesh import STAGE_AXIS
from defer_tpu.runtime.decode import PipelinedDecoder


def main() -> int:
    # a program compiled for a described chip cannot be read back from
    # the persistent cache without the chip: keep it out
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    mb, plen = 64, 32
    graph = gpt(12, 768, 12, 512, vocab=50257)
    params = jax.tree.map(lambda s: np.zeros(s.shape, np.float32),
                          jax.eval_shape(graph.init, jax.random.key(0)))
    dec = PipelinedDecoder(graph, params, num_stages=1, microbatch=mb,
                           max_len=512, compute_dtype=jnp.bfloat16)
    # the program as the chip would get it: same function, the mesh made
    # of the described device, shapes in place of arrays
    dec.mesh = Mesh(np.array(topo.devices[:1]).reshape(dec.mesh.devices.shape),
                    dec.mesh.axis_names)

    def arg(shape, dtype, spec=P()):
        return jax.ShapeDtypeStruct(
            shape, dtype, sharding=NamedSharding(dec.mesh, spec))

    # the format's buffers behind the ring's own stage axis
    caches = {key: (arg((1,) + buf.shape, buf.dtype,
                        P(STAGE_AXIS, *(None,) * len(buf.shape))),)
              * dec.l_max
              for key, buf in dec.kv_format.buffers(mb).items()}
    i32 = arg((), jnp.int32)
    _, chunk_steps = dec._schedule(plen + 128, 0, 32)
    compiled = dec._build_decode_fn(chunk_steps, False, None).lower(
        arg(dec._w.shape, dec._w.dtype, P(STAGE_AXIS, None)),
        arg((1, mb, plen), jnp.int32, P(None, None, None)),
        i32, i32, i32, arg((), jnp.uint32), arg((), jnp.float32),
        arg((1, mb), jnp.int32, P(None, None)), i32, i32,
        arg((1, mb, dec.d_model), jnp.float32,
            P(STAGE_AXIS, None, None)), caches).compile()
    text = compiled.as_text()
    mem = compiled.memory_analysis()
    row = {"device_kind": topo.devices[0].device_kind,
           "row_writes": text.count("dynamic-update-slice"),
           "whole_cache_copies": len(re.findall(
               r"remat_(?:un)?compressed[\w.]* = ", text)),
           "argument_bytes": mem.argument_size_in_bytes,
           "temp_bytes": mem.temp_size_in_bytes}
    print(json.dumps(row))
    return 0 if row["row_writes"] and not row["whole_cache_copies"] else 1


if __name__ == "__main__":
    sys.exit(main())
