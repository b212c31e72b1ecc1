"""The LFM2-24B-A2B cell's decode and prefill programs under the TPU's
own compiler, at the cell's size (layers 0-9 at published widths — 8
gated short-convolution layers, 2 attention layers, 2 dense SwiGLUs and
8 routed layers of 64 experts, all held — 128 sequences, 512 in, 2560
positions, bf16, 32 tokens a call) — no chip needed, not part of the
tests.

What it answers before any chip time is spent:

* do the programs fit one v5e by the compiler's own count
  (``memory_analysis``: 10.80 GB of weights, 2.68 GB of cache rows with
  the scratch group, 8 MB of windows, and what the compiler adds; the
  prefill crosses the stage 16 sequences at a time —
  ``PipelinedDecoder._prefill_rows`` — because the widest activation is
  a dense layer's 11776 columns);
* **how large the compiler makes the windows' arguments**: each
  argument's bytes are counted from the layout the compiled program
  gives it, tiles and all, and their sum is held to 1.10 of the need
  (128 x 8 x 2 x 2048 x 2 B = 8.4 MB);
* does either program *produce* an array the size of a weight matrix,
  of a layer's experts or of a cache buffer inside a loop, or convert a
  weight's layout at the head of a dispatch
  (``scripts/hlo_cache_ops.py``; a decode step rewrites a layer's
  window whole, by design, and nothing else);
* which cache and flash kernels the programs hold, by name, and how
  many ``transpose`` and ``copy`` operations stand in the decode loop.
  Since PR 66 the two attention layers' group of 4 queries over 8 KV
  heads of 64 holds *joined* rows (two heads a lane row, ``bf16[1, 2,
  128, 2560, 512]`` a buffer): the decode program must hold two
  ``kv_attend`` calls and no ``kv_step``, with
  ``decode.kv.joined_layers`` 2, ``decode.kv.fused_layers`` 0 and the
  attention's block ``(1, 1024)`` (``decode.cache.block_sequences`` /
  ``.block_positions``), and the cache's arguments no more bytes than
  the rows themselves;
* does the decode program hold two ``grouped_experts`` calls a routed
  layer and the prefill two ``grouped_rows`` calls.

    env JAX_PLATFORMS=cpu python scripts/lfm2_tpu_compile_check.py

A few minutes and ~12 GB of host memory (the weights are zeros); one
JSON line; exit 0 when both programs fit under 15.3 GB, the windows'
arguments stay within 1.10 of the need and nothing weight-sized or
buffer-sized is produced inside a loop, and the decode program's cache
kernels and gauges are those above.  ``LFM2_CHECK_DUMP=DIR`` writes
both compiled texts.  A process of its own, like the other compile
checks: the TPU's library is locked machine-wide while it runs.
"""

import json
import math
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

from defer_tpu.models import lfm2_moe
from defer_tpu.obs.registry import REGISTRY
from defer_tpu.ops.layered import shapes_by_layer
from defer_tpu.parallel.mesh import STAGE_AXIS
from defer_tpu.runtime.decode import PipelinedDecoder
from hlo_cache_ops import (GroupedCounters, computations, count_cache_ops,
                           grouped_products, weight_copies)
from jamba_tpu_compile_check import argument_bytes
from mellum_tpu_compile_check import off_default_leaves

HERE = os.path.dirname(os.path.abspath(__file__))
LIMIT_GB = 15.3
#: the most the compiler's window arguments may take over the need
WINDOW_OVER_NEED = 1.10
KERNELS = ("kv_step", "kv_attend", "flash_causal", "flash_grouped",
           "flash_band")


def main() -> int:
    jax.config.update("jax_enable_compilation_cache", False)
    with open(os.path.join(HERE, "..", "chipbench", "configs",
                           "lfm2-24b-a2b-10l.json")) as f:
        args = json.load(f)["model_args"]
    with open(os.path.join(HERE, "..", "chipbench", "traffic",
                           "batch128_512in_2048out_chunk32.json")) as f:
        tr = json.load(f)
    mb, plen, max_len, chunk = (tr["batch"], tr["prompt_len"],
                                tr["max_len"], tr["token_chunk"])
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    graph = lfm2_moe(**args)
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
    shapes = shapes_by_layer(dec.state_formats, mb)
    caches = jax.tree.map(
        lambda s: arg((1,) + s.shape, s.dtype,
                      P(STAGE_AXIS, *(None,) * len(s.shape))), shapes)
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
    conv = sum(kind == "conv_window" for kind in dec.memory)
    # (keys and values have one shape: counted together under ``k``)
    buffers = {key: next(s for s in shapes[key] if s is not None).shape
               for key in shapes if key != "v"}
    need = conv * mb * args["hidden"] * (args["d_conv"] - 1) * 2
    row = {"device_kind": topo.devices[0].device_kind,
           "prefill_rows_a_piece": dec._prefill_rows(plen),
           "conv_layers": conv, "window_need_mb": need / 1e6,
           "off_default": off_default_leaves(topo.devices[0], w)}
    matrices = [leaf.shape for leaf in jax.tree.leaves(params)
                if leaf.ndim > 1 and leaf.size > 1 << 22]
    # a piece of the prefill is 8192 tokens: an activation [tokens,
    # columns] may have the shape of a dense matrix, and a shape that
    # activations share says nothing.  The prefill is held to the
    # matrices no activation resembles; the decode program, 128 rows a
    # step, to all of them
    tokens = dec._prefill_rows(plen) * plen
    distinct = [shape for shape in matrices if tokens not in shape]
    ok = True
    out_dir = os.environ.get("LFM2_CHECK_DUMP")
    for name, lowered in (("prefill", prefill), ("decode", decode)):
        try:
            compiled = lowered.compile()
        except Exception as e:  # noqa: BLE001 — the compiler's own refusal
            row[name] = {"refused": str(e)[:6000]}
            ok = False
            continue
        text = compiled.as_text()
        if out_dir:
            with open(os.path.join(out_dir, f"lfm2_{name}.txt"), "w") as f:
                f.write(text)
        m = compiled.memory_analysis()
        comps = computations(text)
        held = argument_bytes(comps["ENTRY"], buffers)
        # a layer's buffer of one group and the whole buffer
        state_ops = {key: count_cache_ops(comps, shape[1:], shape)
                     for key, shape in buffers.items()}
        copies = weight_copies(
            comps, distinct if name == "prefill" else matrices)
        total = (m.argument_size_in_bytes + m.temp_size_in_bytes
                 + m.output_size_in_bytes - m.alias_size_in_bytes) / 1e9
        row[name] = {
            "argument_gb": m.argument_size_in_bytes / 1e9,
            "temp_gb": m.temp_size_in_bytes / 1e9,
            "output_gb": m.output_size_in_bytes / 1e9,
            "alias_gb": m.alias_size_in_bytes / 1e9,
            # the program's own text, which the chip holds too
            "code_mb": m.generated_code_size_in_bytes / 1e6,
            "peak_gb": total, **copies,
            "state_argument_mb": {k: v / 1e6 for k, v in held.items()},
            "window_over_need": held["conv"] / need,
            "state_ops": state_ops,
            "kernels": text.count('custom_call_target="tpu_custom_call"'),
            "kernels_by_name": {k: len(re.findall(
                rf"%{k}(?:\.\d+)? = ", text)) for k in KERNELS},
            # what stands around the kernels: transposes and copies the
            # compiler added, the whole text's
            "transposes": len(re.findall(r" transpose\(", text)),
            "copies": len(re.findall(r" copy\(", text)),
            # the shape rule (defer_tpu/ops/grouped.py): a step's
            # products on the kernel, the prompt's on the tiled one
            **grouped_products(text), **rule[name].read,
            "flops": float(compiled.cost_analysis().get("flops", 0.0))}
        # a decode step rewrites a layer's window whole, by design (one
        # fusion a convolution layer produces it); nothing else may
        # produce an array of a window's or a cache buffer's size
        allowed = {"conv": conv if name == "decode" else 0}
        ok = ok and total <= LIMIT_GB \
            and held["conv"] <= WINDOW_OVER_NEED * need \
            and not copies["weight_copies_in_loop"] \
            and not row[name]["ragged_dots"] \
            and all(c["buffer_copies"] <= allowed.get(key, 0)
                    and not c["item_copies"]
                    for key, c in state_ops.items())
        if name == "decode":
            # the two attention layers' steps: a slice of a position's
            # rows written, one kernel over the joined rows where they
            # lie (the gauges were set as the decode program was traced)
            said = {key: int(REGISTRY.gauge("decode." + key).value)
                    for key in ("kv.joined_layers", "kv.fused_layers")}
            rows = next(f for f in dec.state_formats if hasattr(f, "joined"))
            said.update({key: value for key, value
                         in rows.gauges(mb, 1).items()
                         if key.startswith("decode.cache.block_")})
            row[name]["gauges"] = said
            attention = len(dec.memory) - conv
            rows_mb = 2 * attention * math.prod(buffers["k"]) * 2 / 1e6
            ok = ok and said == {
                "kv.joined_layers": attention, "kv.fused_layers": 0,
                "decode.cache.block_sequences": 1,
                "decode.cache.block_positions": 1024} \
                and row[name]["kernels_by_name"]["kv_attend"] == attention \
                and not row[name]["kernels_by_name"]["kv_step"] \
                and row[name]["state_argument_mb"]["k"] <= rows_mb
    print(json.dumps(row))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
