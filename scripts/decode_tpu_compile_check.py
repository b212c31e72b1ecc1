"""The decode program under the TPU's own compiler, at a size that shows
the cliff (docs/DECODE_CLIFF.md) — no chip needed, not part of the tests.

The compiler for a *described* v5e (``jax.experimental.topologies``) runs
on this host.  r05's cliff row (gpt 12L/768, 64 sequences, 512 positions:
1.1 GiB of bf16 cache) took 558 ms a step on the chip while the ring kept
its blocks' caches in one stacked array: XLA:TPU wrapped every cache
write in a ``remat_uncompressed``/``remat_compressed`` pair of copies of
the whole stack, which shows in the compiled text (214 of them at the
batch cell's size on PR 24's tree).  With per-block buffers there is
none.  Until PR 29 a step also cut a group's item out of every buffer
(``slice``), and the compiled loop converted every buffer to a padded
layout of its own and back, every dispatch (``copy``, and a temporary of
all of them): with the attention a kernel over the buffers as they lie
(``ops/kv_cache.py::kv_attend``) there is neither.  From PR 54 to PR 67
a layer's step under a lane row was one kernel that wrote while it
attended (``kv_step``); since PR 68 heads of 64 on the ring hold joined
rows, a position's write is a slice and the attention the matrix
unit's (``kv_attend``), and ``kv_step`` is narrower heads': the line
counts the cache kernels by name (``cache_kernels``: ``kv_attend`` 24,
``kv_step`` 0 at the batch cell with 48 ``row_writes``; all four
stages' branches at the four-chip cell: 48 and 104) and reads the gauges
``decode.kv.fused_layers`` and ``decode.kv.joined_layers`` (0 and a
stage's layers).  Run it before spending
chip time on a change to how the ring holds its caches — after one to
the cache kernels also at a buffer of under 128 positions (``2 768 12 2
64 4``, seconds: there a block is the whole buffer, 80 rows; while
those heads were ``kv_step``'s a block was one partial lane row, and
Mosaic refused PR 54's first ``kv_step`` for a lane slice it could not
see was aligned, which only the chip smoke's small decoder showed):

    env JAX_PLATFORMS=cpu python scripts/decode_tpu_compile_check.py \\
        [layers d_model heads sequences max_len token_chunk stages]

(default: the cliff row, ``12 768 12 64 512 32``; the benchmark's batch
cell is ``24 1600 25 8 768 4``, gpt2-xl at full depth ``48 1600 25 8 768
4``; the four-chip cell is ``48 1600 25 2 768 4 4``, ``sequences`` being
a group's, under ``XLA_FLAGS=--xla_force_host_platform_device_count=4``).
~30 s at the default; one JSON line; exit 0 when the compiled text
writes rows in place and holds no whole-cache copy, no item-sized slice
or copy inside a step, no whole-buffer conversion around the loop and,
inside the loop, no copy of a weight matrix (a leaf cut out of a flat
row of weights is laid out anew every step: the ring hands every leaf
over as an argument of its own), 1 otherwise (2 when the
program does not fit the chip).  A process
of its own on purpose: loading the TPU's library takes a machine-wide
lock (``/tmp/libtpu_lockfile``) that is held until the process ends, so
this must not live in a long test run.
"""

import json
import os
import re
import sys
from unittest import mock

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax
import jax.numpy as jnp
from jax.experimental import topologies
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from defer_tpu.models import gpt
from defer_tpu.obs.registry import REGISTRY
from defer_tpu.parallel.mesh import STAGE_AXIS
from defer_tpu.runtime.decode import PipelinedDecoder
from hlo_cache_ops import computations, count_cache_ops, weight_copies


def main(layers=12, d_model=768, heads=12, mb=64, max_len=512,
         chunk=32, stages=1) -> int:
    # a program compiled for a described chip cannot be read back from
    # the persistent cache without the chip: keep it out
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    plen = 32
    graph = gpt(layers, d_model, heads, max(max_len, 512), vocab=50257)
    params = jax.tree.map(lambda s: np.zeros(s.shape, np.float32),
                          jax.eval_shape(graph.init, jax.random.key(0)))
    dec = PipelinedDecoder(graph, params, num_stages=stages, microbatch=mb,
                           max_len=max_len, compute_dtype=jnp.bfloat16)
    # the program as the chip would get it: same function, the mesh made
    # of the described devices, shapes in place of arrays
    dec.mesh = Mesh(np.array(topo.devices[:stages]).reshape(
        dec.mesh.devices.shape), dec.mesh.axis_names)

    def arg(shape, dtype, spec=P()):
        return jax.ShapeDtypeStruct(
            shape, dtype, sharding=NamedSharding(dec.mesh, spec))

    # the weights as the decoder holds them: every leaf an argument of
    # its own behind the stage axis, row-major
    # (``PipelinedDecoder.weight_formats``)
    w = jax.tree.map(lambda a, f: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=f), dec._w, dec.weight_formats())
    # the format's buffers behind the ring's own stage axis
    buffers = dec.state_format.buffers(mb)
    caches = {key: (arg((stages,) + buf.shape, buf.dtype,
                        P(STAGE_AXIS, *(None,) * len(buf.shape))),)
              * dec.l_max
              for key, buf in buffers.items()}
    i32 = arg((), jnp.int32)
    _, chunk_steps = dec._schedule(plen + 4 * chunk, 0, chunk)
    # the attention runs its kernel in the interpreter wherever
    # ``jax.default_backend()`` is not the TPU; this host's is the CPU
    # and the program is the chip's
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        lowered = dec._build_decode_fn(chunk_steps, False, None).lower(
            w, arg((stages, mb, plen), jnp.int32, P(None, None, None)),
            i32, i32, i32, arg((), jnp.uint32), arg((), jnp.float32),
            arg((stages, mb), jnp.int32, P(None, None)), i32, i32,
            arg((stages, mb, dec.d_model), jnp.float32,
                P(STAGE_AXIS, None, None)), caches)
    try:
        compiled = lowered.compile()
    except Exception as e:  # noqa: BLE001 — the compiler's own refusal
        print(json.dumps({"device_kind": topo.devices[0].device_kind,
                          "refused": str(e).splitlines()[0][:400]}))
        return 2
    text = compiled.as_text()
    mem = compiled.memory_analysis()
    shape = buffers["k"].shape
    comps = computations(text)
    row = {"device_kind": topo.devices[0].device_kind,
           **weight_copies(comps, [leaf.shape for leaf in
                                   jax.tree.leaves(params) if leaf.ndim > 1]),
           "whole_cache_copies": len(re.findall(
               r"remat_(?:un)?compressed[\w.]* = ", text)),
           **count_cache_ops(comps, shape[1:], shape),
           "kernels": text.count('custom_call_target="tpu_custom_call"'),
           # the cache kernels by name, as a device trace tells them
           "cache_kernels": {name: len(re.findall(
               rf"%{name}[.\d]* = .*tpu_custom_call", text))
               for name in ("kv_step", "kv_attend", "kv_write_rows")},
           "fused_layers": int(REGISTRY.gauge("decode.kv.fused_layers").value),
           "joined_layers": int(
               REGISTRY.gauge("decode.kv.joined_layers").value),
           # blocks a stage, as the bytes cut them (``decode.cut.blocks``)
           "cut": [int(REGISTRY.gauge(f"decode.cut.blocks.{s}").value)
                   for s in range(stages)],
           "argument_bytes": mem.argument_size_in_bytes,
           "temp_bytes": mem.temp_size_in_bytes}
    dump = os.environ.get("DECODE_CHECK_DUMP")
    if dump:
        with open(dump, "w") as f:
            f.write(text)
    print(json.dumps(row))
    return 0 if row["row_writes"] and not (
        row["whole_cache_copies"] or row["item_copies"]
        or row["buffer_copies"] or row["weight_copies_in_loop"]
        or row["weight_copies_per_dispatch"]) else 1


if __name__ == "__main__":
    sys.exit(main(*(int(a) for a in sys.argv[1:])))
