"""Brumby's decode and prefill programs under the TPU's own compiler, at
the size of the benchmark's cell (8 layers at published widths, 16
sequences, 1024 in, 1536 positions, bf16 weights, f32 state) — no chip
needed, not part of the tests.

What it answers before any chip time is spent:

* do the programs fit one v5e (``memory_analysis``: 8.4 GB of weights
  held once, 4.6 GB of retention state, and what the compiler adds);
* does the decode program carry the state through its ``scan`` without
  a copy: no operation but the kernel (``retention_step``, whose output
  aliases its operand) may produce an array of a layer's ``S`` buffer's
  size, nor of one sequence group's (``scripts/hlo_cache_ops.py``
  counts, as for a KV cache);
* is the kernel there, once a layer.

    env JAX_PLATFORMS=cpu python scripts/brumby_tpu_compile_check.py

A few minutes and ~20 GB of host memory (the weights are zeros, placed
on the host's CPU device); one JSON line; exit 0 when the decode program
holds no state-sized copy and produces no weight matrix inside its loop
(``weight_copies_in_loop``; ``weight_copies_per_dispatch`` is reported).
A process of its own, like the other compile checks: the TPU's library
is locked machine-wide while it runs.
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

from defer_tpu.models import brumby
from defer_tpu.parallel.mesh import STAGE_AXIS
from defer_tpu.runtime.decode import PipelinedDecoder
from hlo_cache_ops import computations, count_cache_ops, weight_copies

ARGS = dict(num_layers=8, hidden=5120, heads=40, kv_heads=8,
            mlp_hidden=17408, seq_len=32768, vocab=151936)
MB, PLEN, MAX_LEN, CHUNK = 16, 1024, 1536, 8


def main() -> int:
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    graph = brumby(**ARGS)
    params = jax.tree.map(lambda s: np.zeros(s.shape, jnp.bfloat16),
                          jax.eval_shape(graph.init, jax.random.key(0)))
    # placed on this host's CPU device (the described devices hold no
    # arrays): the decoder reads back how each leaf lies
    dec = PipelinedDecoder(graph, params, num_stages=1, microbatch=MB,
                           max_len=MAX_LEN, compute_dtype=jnp.bfloat16)
    dec.mesh = Mesh(np.array(topo.devices[:1]).reshape(dec.mesh.devices.shape),
                    dec.mesh.axis_names)

    def arg(shape, dtype, spec=P()):
        return jax.ShapeDtypeStruct(
            shape, dtype, sharding=NamedSharding(dec.mesh, spec))

    # the weights as the decoder holds them: every leaf stage-sharded
    # and row-major (``PipelinedDecoder.weight_formats``)
    w = jax.tree.map(lambda a, f: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=f), dec._w, dec.weight_formats())
    buffers = dec.state_format.buffers(MB)
    # the format's buffers behind the ring's own stage axis
    state = {key: (arg((1,) + buf.shape, buf.dtype,
                       P(STAGE_AXIS, *(None,) * len(buf.shape))),)
             * dec.l_max for key, buf in buffers.items()}
    i32, u32, f32 = (arg((), t) for t in (jnp.int32, jnp.uint32, jnp.float32))
    prompt = arg((1, MB, PLEN), jnp.int32, P(None, None, None))

    _, chunk_steps = dec._schedule(MAX_LEN, PLEN, CHUNK)
    # the kernel runs in the interpreter wherever ``jax.default_backend()``
    # is not the TPU; this host's is the CPU and the programs are the chip's
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        prefill = dec._build_prefill_fn(PLEN, False, None).lower(
            w, prompt, u32, f32, state)
        decode = dec._build_decode_fn(chunk_steps, False, None).lower(
            w, prompt, i32, i32, i32, u32, f32,
            arg((1, MB), jnp.int32, P(None, None)), i32, i32,
            arg((1, MB, dec.d_model), jnp.float32,
                P(STAGE_AXIS, None, None)), state)
    row = {"device_kind": topo.devices[0].device_kind}
    out_dir = os.environ.get("BRUMBY_CHECK_DUMP")
    texts = {}
    for name, lowered in (("decode", decode), ("prefill", prefill)):
        try:
            compiled = lowered.compile()
        except Exception as e:          # e.g. the program does not fit
            row[name] = {"error": str(e)[:600]}
            continue
        m = compiled.memory_analysis()
        texts[name] = compiled.as_text()
        row[name] = {"argument_gb": m.argument_size_in_bytes / 1e9,
                     "temp_gb": m.temp_size_in_bytes / 1e9,
                     "output_gb": m.output_size_in_bytes / 1e9,
                     "alias_gb": m.alias_size_in_bytes / 1e9,
                     "retention_step_calls": len(re.findall(
                         r"%retention_step[.\d]* = .*tpu_custom_call",
                         texts[name]))}
        if out_dir:
            with open(os.path.join(out_dir, f"brumby_{name}.txt"), "w") as f:
                f.write(texts[name])
    ok = "decode" in texts and "prefill" in texts
    if "decode" in texts:
        groups, b, kv, rows, d = buffers["S"].shape
        comps = computations(texts["decode"])
        # as the format holds a layer's ``S`` and as the kernel views it
        for tag, item in (("held", (b, kv, rows, d)),
                          ("tiled", (b, kv, rows // 8, 8, d))):
            ops = count_cache_ops(comps, item, (groups,) + item)
            row[f"decode_state_ops_{tag}"] = ops
            ok = ok and not (ops["item_copies"] or ops["buffer_copies"])
        ok = ok and row["decode"]["retention_step_calls"] == dec.l_max
        # a weight matrix produced inside the loop (every step) or in
        # front of it (every dispatch: a layout the loop reads otherwise
        # than the caller holds the leaf)
        copies = weight_copies(comps, [leaf.shape for leaf in
                                       jax.tree.leaves(params)
                                       if leaf.ndim > 1])
        row["decode"].update(copies)
        ok = ok and not copies["weight_copies_in_loop"]
    print(json.dumps(row))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
