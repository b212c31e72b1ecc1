"""The decode engine's step program under the TPU's own compiler, at the
serving cell's size (docs/DECODE_CLIFF.md, "The engine") — no chip
needed, not part of the tests.  The engine's twin of
``decode_tpu_compile_check.py``.

``gpt2xl_chat_serve`` runs ``ContinuousBatchEngine`` at gpt2-xl (48
layers, d 1600, 25 heads), width 16, ``max_len`` 192, f32.  While the
engine kept a stage's caches in one stacked array and wrote rows through
``jax.vmap``, every layer of every step cut a whole cache item out of
the stack, copied it to a scatter's layout and back and wrote it into
the stack again: 23 ms of a 38 ms step (ledger, PR 26).  All of that
shows in the compiled text as operations that *produce* an array of a
cache buffer's size.  With one buffer a layer and each slot's row
written in place (``ops/kv_cache.py``) there is none.  Run it before
spending chip time on a change to how the engine holds or writes its
caches:

    env JAX_PLATFORMS=cpu python scripts/engine_tpu_compile_check.py

~1 min, ~2 GB of host memory (the engine's own zeroed caches; the
weights are shapes only); one JSON line; exit 0 when the step writes
rows in place and no operation of the entry computation produces a
buffer-sized array (the compiler's own prefetches into fast memory are
counted apart), 1 otherwise.  A process of its own on purpose: loading
the TPU's library takes a machine-wide lock (``/tmp/libtpu_lockfile``)
that is held until the process ends, so this must not live in a long
test run.
"""

import json
import os
import re
import sys
from unittest import mock

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
from jax.experimental import topologies
from jax.sharding import SingleDeviceSharding

from defer_tpu.models import gpt
from defer_tpu.serve.engine import ContinuousBatchEngine

N_LAYER, WIDTH, MAX_LEN = 48, 16, 192

#: ``%name = f32[16,25,192,64]{...} opcode(operands), attrs``
_INSTR = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = (\w+)\[([\d,]*)\]\S* "
                    r"([\w\-]+)\((.*)$")
#: opcodes that name or alias an array and move nothing
_FREE = {"parameter", "bitcast", "get-tuple-element"}
#: the two ends of an asynchronous move; a sliced prefetch joins its
#: parts with a ``ConcatBitcast`` custom call
_ASYNC = {"copy-start", "copy-done", "slice-start", "slice-done"}


def _computations(text: str) -> dict[str, list[str]]:
    """HLO text -> {computation name: its instruction lines}."""
    comps: dict[str, list[str]] = {}
    cur = None
    for line in text.splitlines():
        head = re.match(r"^(ENTRY )?%?([\w.\-]+) \(.*\{\s*$", line)
        if head:
            cur = comps.setdefault(
                "ENTRY" if head.group(1) else head.group(2), [])
        elif line.startswith("}"):
            cur = None
        elif cur is not None:
            cur.append(line)
    return comps


def _root_opcode(lines: list[str]) -> str:
    for line in lines:
        if line.lstrip().startswith("ROOT "):
            m = _INSTR.match(line)
            return m.group(4) if m else ""
    return ""


def count_cache_ops(comps: dict[str, list[str]],
                    item_dims: tuple[int, ...]) -> dict:
    """Row writes, item-sized products and the compiler's own prefetches
    among the entry computation's operations whose result is as large as
    a cache buffer (its dimensions in any order, or a stack of them).

    A *row write* runs in place on its operand's buffer: the row-writer
    kernel (a custom call whose output aliases an operand) or a
    ``dynamic-update-slice``, bare or as a fusion's root.  A *prefetch*
    is an asynchronous move the compiler's memory-space assignment adds
    on its own, into fast memory and back (``S(1)``; at the cell's size
    layer 0's two buffers).  An *item copy* is anything else: a slice
    out of a stack, a layout copy, a transpose, a scatter."""
    want = sorted(item_dims)
    row_writes, prefetches, copies = 0, 0, []
    for line in comps.get("ENTRY", []):
        m = _INSTR.match(line)
        if not m:
            continue
        name, _dtype, dims, opcode, rest = m.groups()
        if opcode in _FREE or not dims:
            continue
        got = sorted(int(d) for d in dims.split(","))
        if got != want and not (len(got) == len(want) + 1 and all(
                d in got for d in want)):
            continue
        if opcode == "fusion":
            called = re.search(r"calls=%?([\w.\-]+)", rest)
            opcode = _root_opcode(comps.get(called.group(1), [])) \
                if called else opcode
        in_place = opcode == "dynamic-update-slice" or (
            opcode == "custom-call" and "output_to_operand_aliasing" in rest)
        if in_place and got == want:
            row_writes += 1
        elif opcode in _ASYNC or "ConcatBitcast" in rest:
            prefetches += 1
        else:
            copies.append(name)
    return {"row_writes": row_writes, "item_copies": len(copies),
            "item_copy_kinds": sorted(
                {re.sub(r"[.\d]+$", "", n) for n in copies}),
            "item_prefetches": prefetches}


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

    def vec(dtype):
        return jax.ShapeDtypeStruct((WIDTH,), dtype, sharding=chip)

    # the row-writer runs its kernel in the interpreter wherever
    # ``jax.default_backend()`` is not the TPU; this host's is the CPU
    # and the program is the chip's
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        lowered = eng._step_fn(False).lower(
            params, caches, vec(jnp.int32), vec(jnp.int32),
            vec(jnp.uint32), vec(jnp.float32))
    compiled = lowered.compile()
    comps = _computations(compiled.as_text())
    mem = compiled.memory_analysis()
    row = {"device_kind": topo.devices[0].device_kind,
           **count_cache_ops(comps, item),
           # a table-sized product: the whole ``wte`` laid out anew in
           # front of a 16-row gather
           "table_copies": sum(
               bool(re.search(r"= f32\[50257,1600\]\S* (?!parameter)", ln))
               for ln in comps.get("ENTRY", [])),
           "argument_bytes": mem.argument_size_in_bytes,
           "temp_bytes": mem.temp_size_in_bytes}
    print(json.dumps(row))
    return 0 if row["row_writes"] and not row["item_copies"] else 1


if __name__ == "__main__":
    sys.exit(main())
