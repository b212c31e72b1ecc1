"""OLMoE's decode and prefill programs under the TPU's own compiler, at the
size of the benchmark's cell (8 layers at published widths, 16 sequences,
1024 in, 1280 positions, bf16) — no chip needed, not part of the tests.

What it answers before any chip time is spent (PERF.md, PR 26):

* do the programs fit one v5e (``memory_analysis``: 7.1 GB of weights held
  once, 1.3 GB of caches, and what the compiler adds);
* does the prefill compute 8 experts a token and not 64 (``cost_analysis``
  flops against ``chipbench.roofline_moe.olmoe_prefill_needs``: within
  1.5x);
* is a weight matrix copied anywhere in the decode program's loop (an
  instruction that produces an array of a matrix's size): every matrix
  — the experts', ``q`` / ``k`` / ``v`` / ``proj`` / ``router``, the
  embedding's, the head's — is a stage-sharded argument of its own,
  as every leaf is (PR 44), because a leaf cut out of a flat row of
  weights is one, every step (``weight_copies_in_loop``;
  ``weight_copies_per_dispatch`` counts layout conversions around the
  loop, once a call);
* does a step cut a group's item out of a cache buffer, or the compiled
  loop convert a whole buffer to a layout of its own (both did until
  PR 29: the attention is now a kernel over the buffers as they lie,
  ``ops/kv_cache.py::kv_attend``; ``scripts/hlo_cache_ops.py`` counts).

    env JAX_PLATFORMS=cpu python scripts/olmoe_tpu_compile_check.py

A minute or two and ~8 GB of host memory (the weights are zeros); one
JSON line; exit 0 when all four hold and neither program holds a
``ragged-dot`` (a step's grouped products are ``grouped_experts`` calls,
a prompt's ``grouped_rows`` calls: ``decode_grouped`` / ``prefill_grouped``) (per-dispatch copies are reported,
not refused).  A process of its own, like
``decode_tpu_compile_check.py``: the TPU's library is locked machine-wide
while it runs.
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

from chipbench.roofline_moe import olmoe_prefill_needs
from defer_tpu.models import olmoe
from defer_tpu.parallel.mesh import STAGE_AXIS
from defer_tpu.runtime.decode import PipelinedDecoder
from hlo_cache_ops import (GroupedCounters, computations, count_cache_ops,
                           grouped_products, weight_copies)

ARGS = dict(num_layers=8, hidden=2048, heads=16, seq_len=4096, vocab=50304,
            num_experts=64, experts_per_tok=8, expert_hidden=1024)
MB, PLEN, MAX_LEN, CHUNK = 16, 1024, 1280, 8


def main() -> int:
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    graph = olmoe(**ARGS)
    params = jax.tree.map(lambda s: np.zeros(s.shape, jnp.bfloat16),
                          jax.eval_shape(graph.init, jax.random.key(0)))
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
    # the format's buffers behind the ring's own stage axis
    caches = {key: (arg((1,) + buf.shape, buf.dtype,
                        P(STAGE_AXIS, *(None,) * len(buf.shape))),)
              * dec.l_max
              for key, buf in dec.state_format.buffers(MB).items()}
    i32, u32, f32 = (arg((), t) for t in (jnp.int32, jnp.uint32, jnp.float32))
    prompt = arg((1, MB, PLEN), jnp.int32, P(None, None, None))

    _, chunk_steps = dec._schedule(MAX_LEN, PLEN, CHUNK)
    # the kernels run in the interpreter wherever
    # ``jax.default_backend()`` is not the TPU; this host's is the CPU
    # and the programs are the chip's
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        with GroupedCounters() as prefill_rule:
            prefill = dec._build_prefill_fn(PLEN, False, None).lower(
                w, prompt, u32, f32, caches)
        with GroupedCounters() as decode_rule:
            decode = dec._build_decode_fn(chunk_steps, False, None).lower(
                w, prompt, i32, i32, i32, u32, f32,
                arg((1, MB), jnp.int32, P(None, None)), i32, i32,
                arg((1, MB, dec.d_model), jnp.float32,
                    P(STAGE_AXIS, None, None)), caches)
    prefill, decode = prefill.compile(), decode.compile()

    e, d, h = ARGS["num_experts"], ARGS["hidden"], ARGS["expert_hidden"]
    expert_shapes = (f"bf16[{e},{d},{h}]", f"bf16[{e},{h},{d}]",
                     f"bf16[1,{e},{d},{h}]", f"bf16[1,{e},{h},{d}]")
    text = decode.as_text()
    out_dir = os.environ.get("OLMOE_CHECK_DUMP")
    if out_dir:
        with open(os.path.join(out_dir, "olmoe_decode.txt"), "w") as f:
            f.write(text)
        with open(os.path.join(out_dir, "olmoe_prefill.txt"), "w") as f:
            f.write(prefill.as_text())
    # an instruction that *produces* an expert-sized array (parameters,
    # tuple plumbing and bitcasts move nothing)
    produced = [m.group(0) for m in re.finditer(
        r"^\s*(?:ROOT )?%?[\w.\-]+ = (\S+) ([\w\-]+)\(", text, re.M)
        if m.group(1).split("{")[0] in expert_shapes
        and m.group(2) not in ("parameter", "get-tuple-element", "bitcast")]
    needs_flops, _ = olmoe_prefill_needs(
        n_layer=ARGS["num_layers"], n_embd=d, n_head=ARGS["heads"],
        vocab=ARGS["vocab"], rows=MB, prompt_len=PLEN,
        top_k=ARGS["experts_per_tok"], n_experts=e, expert_width=h,
        weight_bytes=2, kv_bytes=2)
    flops = float(prefill.cost_analysis()["flops"])

    def mem(c):
        m = c.memory_analysis()
        return {"argument_gb": m.argument_size_in_bytes / 1e9,
                "temp_gb": m.temp_size_in_bytes / 1e9,
                "output_gb": m.output_size_in_bytes / 1e9,
                "alias_gb": m.alias_size_in_bytes / 1e9}

    shape = dec.state_format.buffers(MB)["k"].shape
    comps = computations(text)
    cache_ops = count_cache_ops(comps, shape[1:], shape)
    copies = weight_copies(comps, [leaf.shape for leaf in
                                   jax.tree.leaves(params) if leaf.ndim > 1])
    row = {"device_kind": topo.devices[0].device_kind, **copies,
           "prefill": mem(prefill), "decode": mem(decode),
           "decode_cache_ops": cache_ops,
           "prefill_flops": flops, "prefill_needs_flops": needs_flops,
           "prefill_flops_over_needs": flops / needs_flops,
           "expert_sized_values_produced_in_decode": produced[:8],
           # the shape rule (defer_tpu/ops/grouped.py): a step's
           # products on the kernel, the prompt's on the tiled one
           "decode_grouped": {**grouped_products(text), **decode_rule.read},
           "prefill_grouped": {**grouped_products(prefill.as_text()),
                               **prefill_rule.read},
           }
    print(json.dumps(row))
    # (no ``lax.ragged_dot`` is left in either program since PR 56)
    ok = not produced and flops / needs_flops <= 1.5 and not (
        cache_ops["item_copies"] or cache_ops["buffer_copies"]
        or copies["weight_copies_in_loop"]
        or row["decode_grouped"]["ragged_dots"]
        or row["prefill_grouped"]["ragged_dots"])
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
