"""The Nemotron-3-Super cell's decode and prefill programs under the
TPU's own compiler, at the cell's size (layers 25-35 of the published
pattern, ``*EMEMEMEMEM``, at published widths — 1 attention layer, 5
Mamba-2 layers with 8 B/C groups, 5 LatentMoE layers that keep no
memory, 128 of 512 experts held in each — 128 sequences, 512 in, 3584
positions, bf16, 32 tokens a call) — no chip needed, not part of the
tests.

What it answers before any chip time is spent:

* do the programs fit one v5e by the compiler's own count
  (``memory_analysis``: 9.30 GB of weights, 2.72 GB of state-space
  state, 0.94 GB of cache rows with the scratch group, and what the
  compiler adds; the prefill crosses the stage a few sequences at a
  time — ``PipelinedDecoder._prefill_rows`` — because the widest
  activation is the input projection's 18560 columns);
* **how large the compiler makes the state's arguments**: each
  argument's bytes are counted from the layout the compiled program
  gives it, tiles and all, and the state-space buffers' sum is held to
  1.10 of the need (``h`` 128 x 5 x 128 x 8192 x 4 B, the windows 128 x
  5 x 3 x 10240 x 2 B: 2.72 GB); **the five memory-less layers have no
  argument at all** (``memoryless_layers`` 5, state keys ``k``, ``v``,
  ``conv``, ``h`` with None at their places);
* does either program *produce* an array the size of a weight matrix,
  of a layer's experts or of a layer's state or cache buffer inside a
  loop (``scripts/hlo_cache_ops.py``);
* does the decode program hold an ``ssd_step`` call a Mamba layer and
  the prefill an ``ssd_scan`` call a Mamba layer (Mosaic first sees
  both with 8 groups here: a block of 1024 channels reads its group's
  128 columns of ``B`` and ``C``);
* does the decode program hold two ``grouped_experts`` calls an ``E``
  layer (the up product, the down product; relu squared a fusion
  between them) and the prefill two ``grouped_rows`` calls a layer;
* does the decode program hold **one** ``kv_attend`` call, over joined
  rows (``decode.kv.joined_layers`` 1: 16 queries a KV head of 128, rows
  of 2 x 128 = 512 B a position), and neither a copy the size of the
  cache buffer nor a slice the size of a group's item around it.

    env JAX_PLATFORMS=cpu python scripts/nemotron_h_tpu_compile_check.py

A few minutes and ~12 GB of host memory (the weights are zeros); one
JSON line; exit 0 when both programs fit under 15.3 GB, the state's
arguments stay within 1.10 of the need, nothing weight-sized or
state-sized is produced inside a loop, the kernels' counts are as above
and the decode program's one ``kv_attend`` is the joined one.
``NEMOTRON_CHECK_DUMP=DIR`` writes both compiled texts.  A process of
its own, like the other compile checks: the TPU's library is locked
machine-wide while it runs.
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

from defer_tpu.models import nemotron_h
from defer_tpu.obs.registry import REGISTRY
from defer_tpu.ops.layered import shapes_by_layer
from defer_tpu.parallel.mesh import STAGE_AXIS
from defer_tpu.runtime.decode import PipelinedDecoder
from hlo_cache_ops import (GroupedCounters, computations, count_cache_ops,
                           grouped_products, weight_copies)
from jamba_tpu_compile_check import argument_bytes

HERE = os.path.dirname(os.path.abspath(__file__))
LIMIT_GB = 15.3
#: the most the compiler's state-space arguments may take over the need
STATE_OVER_NEED = 1.10

def main() -> int:
    jax.config.update("jax_enable_compilation_cache", False)
    with open(os.path.join(HERE, "..", "chipbench", "configs",
                           "nemotron-3-super-120b-a12b-11l-ep4.json")) as f:
        args = json.load(f)["model_args"]
    with open(os.path.join(HERE, "..", "chipbench", "traffic",
                           "batch128_512in_3072out_chunk32.json")) as f:
        tr = json.load(f)
    mb, plen, max_len, chunk = (tr["batch"], tr["prompt_len"],
                                tr["max_len"], tr["token_chunk"])
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    graph = nemotron_h(**args)
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
    mamba = sum(kind == "ssm" for kind in dec.memory)
    # (keys and values have one shape: counted together under ``k``)
    buffers = {key: next(s for s in shapes[key] if s is not None).shape
               for key in shapes if key != "v"}
    channels = args["mamba_heads"] * args["mamba_head_dim"]
    need = mamba * mb * (
        channels * args["mamba_d_state"] * 4
        + (channels + 2 * args["mamba_groups"] * args["mamba_d_state"])
        * (args["mamba_d_conv"] - 1) * 2)
    routed = sum(kind is None for kind in dec.memory)
    row = {"device_kind": topo.devices[0].device_kind,
           "prefill_rows_a_piece": dec._prefill_rows(plen),
           "mamba_layers": mamba, "state_need_gb": need / 1e9,
           "memoryless_layers": int(
               REGISTRY.gauge("decode.memoryless_layers").value),
           # a memory-less layer has None under every key of the state
           "state_keys": {key: [s is not None for s in shapes[key]]
                          for key in shapes}}
    ok = row["memoryless_layers"] == routed == 5 and all(
        not any(shapes[key][l] is not None for key in shapes)
        for l, kind in enumerate(dec.memory) if kind is None)
    matrices = [leaf.shape for leaf in jax.tree.leaves(params)
                if leaf.ndim > 1 and leaf.size > 1 << 22]
    # a piece of the prefill is 4096 tokens, the stream's own width: an
    # activation [tokens, columns] has the shape of every dense matrix
    # [hidden, columns], and a shape that activations share says
    # nothing.  The prefill is held to the matrices no activation
    # resembles (a layer's experts, the table); the decode program, 64
    # rows a step, to all of them
    tokens = dec._prefill_rows(plen) * plen
    distinct = [shape for shape in matrices if tokens not in shape]
    out_dir = os.environ.get("NEMOTRON_CHECK_DUMP")
    for name, lowered in (("prefill", prefill), ("decode", decode)):
        try:
            compiled = lowered.compile()
        except Exception as e:  # noqa: BLE001 — the compiler's own refusal
            row[name] = {"refused": str(e)[:6000]}
            ok = False
            continue
        text = compiled.as_text()
        if out_dir:
            with open(os.path.join(out_dir, f"nemotron_h_{name}.txt"),
                      "w") as f:
                f.write(text)
        m = compiled.memory_analysis()
        comps = computations(text)
        held = argument_bytes(comps["ENTRY"], buffers)
        state = held["h"] + held["conv"]
        # a layer's buffer of one group (a state has one group on one
        # stage: the item is the buffer) and the whole buffer
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
            "peak_gb": total, **copies,
            "state_argument_gb": {k: v / 1e9 for k, v in held.items()},
            "state_over_need": state / need,
            "state_ops": state_ops,
            "kernels": text.count('custom_call_target="tpu_custom_call"'),
            # the cache kernels by name, as a device trace tells them
            "cache_kernels": {k: len(re.findall(
                rf"%{k}[.\d]* = .*tpu_custom_call", text))
                for k in ("kv_attend", "kv_step", "kv_write_rows")},
            "state_kernels": {k: len(re.findall(
                rf"%{k}[.\d]* = .*tpu_custom_call", text))
                for k in ("ssd_step", "ssd_scan")},
            # the shape rule (defer_tpu/ops/grouped.py): a step's
            # products on the kernel, the prompt's on the tiled one
            **grouped_products(text), **rule[name].read,
            "flops": float(compiled.cost_analysis().get("flops", 0.0))}
        # a decode step rewrites a layer's window whole, by design (one
        # fusion a Mamba layer produces it); nothing else may produce an
        # array of a state's or a cache buffer's size
        allowed = {"conv": mamba if name == "decode" else 0}
        ok = ok and total <= LIMIT_GB and state <= STATE_OVER_NEED * need \
            and not copies["weight_copies_in_loop"] \
            and all(c["buffer_copies"] <= allowed.get(key, 0)
                    and not c["item_copies"]
                    for key, c in state_ops.items())
        want = {"decode": {"ssd_step": mamba, "ssd_scan": 0},
                "prefill": {"ssd_step": 0, "ssd_scan": mamba}}[name]
        products = grouped_products(text)
        ok = ok and row[name]["state_kernels"] == want \
            and products["grouped_experts_calls" if name == "decode"
                         else "grouped_rows_calls"] == 2 * routed
        if name == "decode":
            # the one attention layer's step: a slice of a position's
            # rows written, one kernel over the joined rows where they
            # lie (the gauge was set as the decode program was traced)
            joined = int(REGISTRY.gauge("decode.kv.joined_layers").value)
            row[name]["joined_layers"] = joined
            ok = ok and joined == 1 and row[name]["cache_kernels"] == {
                "kv_attend": 1, "kv_step": 0, "kv_write_rows": 0}
    print(json.dumps(row))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
