"""The command-a-plus cell's decode and prefill programs under the TPU's
own compiler, at the cell's size (4 layers — one period of three window
layers and a full one — at published widths, experts 0-15 of 128 held,
16 sequences, 8192 in, 12288 positions, bf16, 64 tokens a call) — no
chip needed, not part of the tests.

What it answers before any chip time is spent:

* do the programs fit one v5e by the compiler's own count
  (``memory_analysis``: ~9.7 GB of weights held once, the ring buffers
  and the full layer's cache twice over for the scratch group, and what
  the compiler adds; the prefill runs a sequence at a time —
  ``PipelinedDecoder._prefill_rows`` — because 16 x 8192 rows of 128 x
  128 queries are 4.3 GB);
* does either program *produce* an array the size of a weight matrix or
  of a cache buffer inside a loop (``scripts/hlo_cache_ops.py``): every
  matrix is a stage-sharded argument of its own, the attention reads the
  buffers where they lie;
* does the decode program hold a ``kv_attend`` call a layer and a
  grouped product over the 16 held experts.

    env JAX_PLATFORMS=cpu python scripts/cohere_moe_tpu_compile_check.py

A few minutes and ~12 GB of host memory (the weights are zeros); one
JSON line; exit 0 when both programs fit under 15 GB and nothing
weight-sized or buffer-sized is produced inside a loop.  A process of
its own, like the other compile checks: the TPU's library is locked
machine-wide while it runs.
"""

import json
import os
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

from defer_tpu.models import cohere_moe
from defer_tpu.parallel.mesh import STAGE_AXIS
from defer_tpu.runtime.decode import PipelinedDecoder
from hlo_cache_ops import (GroupedCounters, computations, count_cache_ops,
                           grouped_products, weight_copies)

HERE = os.path.dirname(os.path.abspath(__file__))
LIMIT_GB = 15.0


def main() -> int:
    jax.config.update("jax_enable_compilation_cache", False)
    with open(os.path.join(HERE, "..", "chipbench", "configs",
                           "command-a-plus-4l-ep8.json")) as f:
        args = json.load(f)["model_args"]
    with open(os.path.join(HERE, "..", "chipbench", "traffic",
                           "batch16_8192in_4096out_chunk64.json")) as f:
        tr = json.load(f)
    mb, plen, max_len, chunk = (tr["batch"], tr["prompt_len"],
                                tr["max_len"], tr["token_chunk"])
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    graph = cohere_moe(**args)
    params = jax.tree.map(lambda s: np.zeros(s.shape, jnp.bfloat16),
                          jax.eval_shape(graph.init, jax.random.key(0)))
    dec = PipelinedDecoder(graph, params, num_stages=1, microbatch=mb,
                           max_len=max_len, compute_dtype=jnp.bfloat16)
    dec.mesh = Mesh(np.array(topo.devices[:1]).reshape(dec.mesh.devices.shape),
                    dec.mesh.axis_names)

    def arg(shape, dtype, spec=P()):
        return jax.ShapeDtypeStruct(
            shape, dtype, sharding=NamedSharding(dec.mesh, spec))

    # the weights as the decoder holds them: every leaf stage-sharded
    # and row-major (``PipelinedDecoder.weight_formats``)
    w = jax.tree.map(lambda a, f: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=f), dec._w, dec.weight_formats())
    # each layer's own buffers behind the ring's stage axis
    shapes = [fmt.buffers(mb) for fmt in dec.state_formats]
    caches = {key: tuple(arg((1,) + s[key].shape, s[key].dtype,
                             P(STAGE_AXIS, *(None,) * len(s[key].shape)))
                         for s in shapes) for key in shapes[0]}
    i32, u32, f32 = (arg((), t) for t in (jnp.int32, jnp.uint32, jnp.float32))
    prompt = arg((1, mb, plen), jnp.int32, P(None, None, None))

    _, chunk_steps = dec._schedule(max_len, plen, chunk)
    rule = {"prefill": GroupedCounters(), "decode": GroupedCounters()}
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        with rule["prefill"]:
            prefill = dec._build_prefill_fn(plen, False, None).lower(
                w, prompt, u32, f32, caches)
        with rule["decode"]:
            decode = dec._build_decode_fn(chunk_steps, False, None).lower(
                w, prompt, i32, i32, i32, u32, f32,
                arg((1, mb), jnp.int32, P(None, None)), i32, i32,
                arg((1, mb, dec.d_model), jnp.float32,
                    P(STAGE_AXIS, None, None)), caches)
    row = {"device_kind": topo.devices[0].device_kind,
           "prefill_rows_a_piece": dec._prefill_rows(plen)}
    # (the router's 4096 x 128 is the size of a step's 128 sorted rows,
    # and k's and v's 4096 x 1024 that of a prompt's last window of
    # keys: the matrices a copy of which would cost something are the
    # larger ones)
    matrices = [leaf.shape for leaf in jax.tree.leaves(params)
                if leaf.ndim > 1 and leaf.size > 1 << 22]
    ok = True
    out_dir = os.environ.get("COHERE_CHECK_DUMP")
    for name, lowered in (("prefill", prefill), ("decode", decode)):
        try:
            compiled = lowered.compile()
        except Exception as e:  # noqa: BLE001 — the compiler's own refusal
            row[name] = {"refused": str(e)[:6000]}
            ok = False
            continue
        text = compiled.as_text()
        if out_dir:
            with open(os.path.join(out_dir, f"cohere_{name}.txt"), "w") as f:
                f.write(text)
        m = compiled.memory_analysis()
        comps = computations(text)
        cache_ops = {}
        for kind, s in (("window", shapes[0]), ("full", shapes[-1])):
            shape = s["k"].shape
            cache_ops[kind] = count_cache_ops(comps, shape[1:], shape)
        copies = weight_copies(comps, matrices)
        total = (m.argument_size_in_bytes + m.temp_size_in_bytes
                 + m.output_size_in_bytes - m.alias_size_in_bytes) / 1e9
        row[name] = {
            "argument_gb": m.argument_size_in_bytes / 1e9,
            "temp_gb": m.temp_size_in_bytes / 1e9,
            "output_gb": m.output_size_in_bytes / 1e9,
            "alias_gb": m.alias_size_in_bytes / 1e9,
            "peak_gb": total, **copies, "cache_ops": cache_ops,
            "kernels": text.count('custom_call_target="tpu_custom_call"'),
            # the shape rule (defer_tpu/ops/grouped.py): a step's
            # products on the kernel, the prompt's on the tiled one
            **grouped_products(text), **rule[name].read,
            "flops": float(compiled.cost_analysis().get("flops", 0.0))}
        ok = ok and total <= LIMIT_GB and not copies["weight_copies_in_loop"] \
            and not any(c["buffer_copies"] for c in cache_ops.values())
        if name == "decode":
            ok = ok and not any(c["item_copies"] for c in cache_ops.values())
    print(json.dumps(row))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
