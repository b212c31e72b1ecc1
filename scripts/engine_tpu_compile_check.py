"""The decode engine's step and prefill programs under the TPU's own
compiler, at the serving cell's size (docs/DECODE_CLIFF.md, "The
engine") — no chip needed, not part of the tests.  The engine's twin of
``decode_tpu_compile_check.py``.

``gpt2xl_chat_serve`` runs ``ContinuousBatchEngine`` at gpt2-xl (48
layers, d 1600, 25 heads), width 16, ``max_len`` 192, f32.  While the
engine kept a stage's caches in one stacked array and wrote rows through
``jax.vmap``, every layer of every step cut a whole cache item out of
the stack, copied it to a scatter's layout and back and wrote it into
the stack again: 23 ms of a 38 ms step (ledger, PR 26).  All of that
shows in the compiled text as operations that *produce* an array of a
cache buffer's size.  With one buffer a layer and each slot's row
written in place (``ops/kv_cache.py``) there is none.  The prefill
program (a joining request's prompt, 128 positions, through 8 layers
into one slot's rows: the format's ``write_prefix``; the engine calls
it once a group of blocks) is held to the same: its 16 bulk writes in
place, and no copy of a buffer around any of them.  Run it before
spending chip time on a change to how the engine holds or writes its
caches:

    env JAX_PLATFORMS=cpu python scripts/engine_tpu_compile_check.py

~1 min, ~2 GB of host memory (the engine's own zeroed caches; the
weights are shapes only); one JSON line, the step's counts and under
``prefill_group`` the prefill program's; exit 0 when both write rows in
place and no operation produces a buffer-sized array (the compiler's
own prefetches into fast memory are counted apart), 1 otherwise.  A
process of its own on purpose: loading the TPU's library takes a
machine-wide lock (``/tmp/libtpu_lockfile``) that is held until the
process ends, so this must not live in a long test run.
"""

import json
import os
import re
import sys
from unittest import mock

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
from jax.experimental import topologies
from jax.sharding import SingleDeviceSharding

from defer_tpu.models import gpt
from defer_tpu.serve.engine import PREFILL_LAYERS, ContinuousBatchEngine
from hlo_cache_ops import computations, count_cache_ops

N_LAYER, WIDTH, MAX_LEN = 48, 16, 192

def main() -> int:
    # a program compiled for a described chip cannot be read back from
    # the persistent cache without the chip: keep it out
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    graph = gpt(N_LAYER, 1600, 25, 1024, vocab=50257)
    # the step reads its weights as an argument: the engine itself holds
    # none here, and the program gets their shapes
    eng = ContinuousBatchEngine(graph, {}, num_stages=1, width=WIDTH,
                                max_len=MAX_LEN)

    def shaped(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip)

    params = jax.tree.map(shaped,
                          jax.eval_shape(graph.init, jax.random.key(0)))
    buffers = eng.kv_format.buffers(WIDTH)
    caches = {key: (shaped(buf),) * N_LAYER
              for key, buf in buffers.items()}
    item = buffers["k"].shape

    # the step's per-slot rows: the ids the step before returned and
    # the host's six (the last the list of live slots, which both cache
    # kernels take as a scalar-prefetch operand: Mosaic sees them here)
    rows = [shaped(x) for x in (eng._prev_ids, *eng._blank_rows())]

    def report(lowered) -> dict:
        compiled = lowered.compile()
        text = compiled.as_text()
        comps = computations(text)
        mem = compiled.memory_analysis()
        return {**count_cache_ops(comps, item),
                # the step: one row-writer a buffer and one attention a
                # layer; the prefill: a flash attention a layer
                "kernels": text.count(
                    'custom_call_target="tpu_custom_call"'),
                # a table-sized product: the whole ``wte`` laid out anew
                # in front of a gather of a few rows
                "table_copies": sum(
                    bool(re.search(r"= f32\[50257,1600\]\S* (?!parameter)",
                                   ln))
                    for ln in comps.get("ENTRY", [])),
                "argument_bytes": mem.argument_size_in_bytes,
                "temp_bytes": mem.temp_size_in_bytes}

    # the row-writer and the attentions run their kernels in the
    # interpreter wherever
    # ``jax.default_backend()`` is not the TPU; this host's is the CPU
    # and the program is the chip's
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        step = eng._step_fn(False).lower(params, caches, *rows)
        # the first group of blocks' prefill: the engine calls the
        # program once a group
        ops, names = zip(*eng._blocks[:PREFILL_LAYERS])
        prefill = eng._prefill_fns[1].lower(
            ops, [params[nm] for nm in names],
            jax.ShapeDtypeStruct((1, eng.prefill_len, 1600), jnp.float32,
                                 sharding=chip),
            [{key: shaped(buf) for key, buf in buffers.items()}
             for _ in ops],
            jax.ShapeDtypeStruct((), jnp.int32, sharding=chip))
    row = {"device_kind": topo.devices[0].device_kind, **report(step),
           "prefill_group": {"positions": eng.prefill_len,
                             "layers": len(ops), **report(prefill)}}
    print(json.dumps(row))
    return 0 if all(r["row_writes"] and not (
        r["item_copies"] or r["buffer_copies"])
        for r in (row, row["prefill_group"])) else 1


if __name__ == "__main__":
    sys.exit(main())
