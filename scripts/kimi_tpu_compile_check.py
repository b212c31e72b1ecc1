"""The Kimi-K2.7-Code cell's decode and prefill programs under the TPU's
own compiler, at the cell's size (the dense layer and 4 routed layers at
published widths, 12 of 384 experts held in each, 32 sequences, 8192 in,
12288 positions, bf16, 32 tokens a call) — no chip needed, not part of
the tests.

What it answers before any chip time is spent:

* does Mosaic take the two new kernels at these shapes (``latent_attend``:
  64 absorbed queries over row blocks 640 columns wide; ``flash_latent``:
  a 64-wide shared key beside 128-wide own keys and values);
* do the programs fit one v5e by the compiler's own count
  (``memory_analysis``: 6.99 GB of weights, 5.04 GB of latent rows with
  the scratch group, and what the compiler adds; the prefill crosses the
  stage one sequence at a time — ``PipelinedDecoder._prefill_rows`` —
  because the widest activation is the dense layer's 18432 columns);
* **how large the compiler makes the cache's arguments**: each
  argument's bytes are counted from the layout the compiled program
  gives it, tiles and all, and their sum is held to 1.12 of the need
  (1152 B a row: 576 bfloat16 values);
* does either program *produce* an array the size of a weight matrix,
  of a layer's experts or of a layer's cache buffer inside a loop
  (``scripts/hlo_cache_ops.py``);
* does the decode program hold a ``latent_attend`` call a layer and the
  prefill a ``flash_latent`` call a layer.

    env JAX_PLATFORMS=cpu python scripts/kimi_tpu_compile_check.py

A few minutes and ~10 GB of host memory (the weights are zeros); one
JSON line; exit 0 when both programs fit under 16 GB, the cache's
arguments stay within 1.12 of the need and nothing weight-sized or
buffer-sized is produced inside a loop.  ``KIMI_CHECK_DUMP=DIR`` writes
both compiled texts.  A process of its own, like the other compile
checks: the TPU's library is locked machine-wide while it runs.
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

from defer_tpu.models import kimi_k2
from defer_tpu.ops.layered import shapes_by_layer
from defer_tpu.parallel.mesh import STAGE_AXIS
from defer_tpu.runtime.decode import PipelinedDecoder
from hlo_cache_ops import (GroupedCounters, computations, count_cache_ops,
                           grouped_products, weight_copies)
from jamba_tpu_compile_check import argument_bytes

HERE = os.path.dirname(os.path.abspath(__file__))
LIMIT_GB = 16.0
#: the most the compiler's cache arguments may take over the need
CACHE_OVER_NEED = 1.12


def check(model, config: str, traffic: str, tag: str) -> int:
    """Compile the cell of ``config`` / ``traffic`` (file names under
    ``chipbench/``) for the graph ``model(**model_args)``; the module
    docstring's checks, one JSON line, the exit code.
    ``<TAG>_CHECK_DUMP=DIR`` writes both compiled texts as
    ``<tag>_<program>.txt``.  Any family of latent blocks
    (``scripts/longcat_tpu_compile_check.py`` calls it too)."""
    jax.config.update("jax_enable_compilation_cache", False)
    with open(os.path.join(HERE, "..", "chipbench", "configs", config)) as f:
        args = json.load(f)["model_args"]
    with open(os.path.join(HERE, "..", "chipbench", "traffic", traffic)) as f:
        tr = json.load(f)
    mb, plen, max_len, chunk = (tr["batch"], tr["prompt_len"],
                                tr["max_len"], tr["token_chunk"])
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    graph = model(**args)
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
    # a row buffer a sublayer a layer, all of one shape
    layers = sum(fmt.sublayers for fmt in dec.state_formats)
    buffer = shapes["latent"][0].shape
    # the scratch group and row are the ring's, the row's need the
    # configuration's: 576 values of 2 bytes
    need = layers * int(np.prod(buffer[:-1])) \
        * (args["latent_dim"] + args["rope_dim"]) * 2
    row = {"device_kind": topo.devices[0].device_kind,
           "prefill_rows_a_piece": dec._prefill_rows(plen),
           "cache_buffer": list(buffer), "cache_need_gb": need / 1e9}
    matrices = [leaf.shape for leaf in jax.tree.leaves(params)
                if leaf.ndim > 1 and leaf.size > 1 << 22]
    # a piece of the prefill is a prompt's tokens: an activation [tokens,
    # columns] may have the shape of a dense matrix, and a shape that
    # activations share says nothing.  The prefill is held to the
    # matrices no activation resembles, the decode program to all
    tokens = dec._prefill_rows(plen) * plen
    distinct = [shape for shape in matrices if tokens not in shape]
    ok = True
    out_dir = os.environ.get(f"{tag.upper()}_CHECK_DUMP")
    for name, lowered in (("prefill", prefill), ("decode", decode)):
        try:
            compiled = lowered.compile()
        except Exception as e:  # noqa: BLE001 — the compiler's own refusal
            row[name] = {"refused": str(e)[:6000]}
            ok = False
            continue
        text = compiled.as_text()
        if out_dir:
            os.makedirs(out_dir, exist_ok=True)
            with open(os.path.join(out_dir, f"{tag}_{name}.txt"), "w") as f:
                f.write(text)
        m = compiled.memory_analysis()
        comps = computations(text)
        held = argument_bytes(comps["ENTRY"], {"latent": buffer})["latent"]
        cache_ops = count_cache_ops(comps, buffer[1:], buffer)
        copies = weight_copies(
            comps, distinct if name == "prefill" else matrices)
        total = (m.argument_size_in_bytes + m.temp_size_in_bytes
                 + m.output_size_in_bytes - m.alias_size_in_bytes) / 1e9
        row[name] = {
            "argument_gb": m.argument_size_in_bytes / 1e9,
            "temp_gb": m.temp_size_in_bytes / 1e9,
            "output_gb": m.output_size_in_bytes / 1e9,
            "alias_gb": m.alias_size_in_bytes / 1e9,
            "peak_gb": total, **copies,
            "cache_argument_gb": held / 1e9,
            "cache_over_need": held / need,
            "cache_ops": cache_ops,
            "latent_attend_calls": text.count("%latent_attend"),
            "flash_latent_calls": text.count("%flash_latent"),
            **grouped_products(text), **rule[name].read,
            "flops": float(compiled.cost_analysis().get("flops", 0.0))}
        ok = ok and total <= LIMIT_GB and held <= CACHE_OVER_NEED * need \
            and not copies["weight_copies_in_loop"] \
            and not cache_ops["buffer_copies"] \
            and not cache_ops["item_copies"]
    print(json.dumps(row))
    return 0 if ok else 1


def main() -> int:
    return check(kimi_k2, "kimi-k2.7-code-5l-ep32.json",
                 "batch32_8192in_4096out_chunk32.json", "kimi")


if __name__ == "__main__":
    sys.exit(main())
