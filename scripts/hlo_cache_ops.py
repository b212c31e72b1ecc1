"""What the ``*_tpu_compile_check.py`` scripts count in a compiled
program's text: the operations that touch a KV cache buffer (and, at
the end, those that copy a weight matrix).

* a **row write** runs in place on its operand's buffer: the row-writer
  kernel (a custom call whose output aliases an operand) or a
  ``dynamic-update-slice``, bare or as a fusion's root;
* a **prefetch** is an asynchronous move the compiler's memory-space
  assignment adds on its own, into fast memory and back;
* an **item copy** is anything else that *produces* an array of one
  sequence group's size inside a step: a slice out of a buffer, a layout
  copy, a transpose, a scatter;
* a **buffer copy** is anything that produces an array of a whole
  buffer's size: the layout conversion a compiled loop puts around
  itself when it wants a buffer otherwise than its caller holds it.

A size is matched by its dimensions in any order, unit dimensions
aside, so a re-laid-out copy counts.
"""

import re

#: ``%name = f32[16,25,192,64]{...} opcode(operands), attrs``
_INSTR = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = (\w+)\[([\d,]*)\]\S* "
                    r"([\w\-]+)\((.*)$")
#: a custom call of several results: ``%name = (f32[..]{..}, ..)
#: custom-call(operands), attrs`` (a kernel that attends and writes:
#: ``ops/kv_cache.py::kv_step``)
_CALL = re.compile(r"^\s*(?:ROOT )?%?[\w.\-]+ = \((.*?)\) custom-call\((.*)$")
#: opcodes that name or alias an array and move nothing
_FREE = {"parameter", "bitcast", "get-tuple-element", "tuple", "while",
         "conditional", "call", "optimization-barrier"}
#: the two ends of an asynchronous move; a sliced prefetch joins its
#: parts with a ``ConcatBitcast`` custom call
_ASYNC = {"copy-start", "copy-done", "slice-start", "slice-done"}


def computations(text: str) -> dict[str, list[str]]:
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


def _dims(shape) -> list[int]:
    return sorted(int(d) for d in shape if int(d) != 1)


def _fusion_bodies(comps: dict[str, list[str]]) -> set[str]:
    """The computations that are fusions' bodies: their values live in
    registers."""
    return {m.group(1) for lines in comps.values() for line in lines
            for m in [re.search(r"fusion\(.*calls=%?([\w.\-]+)", line)] if m}


def _kind(op_name: str) -> str:
    return re.sub(r"[.\d]+$", "", op_name)


def count_cache_ops(comps: dict[str, list[str]], item_dims, buffer_dims=None,
                    within=None) -> dict:
    """Counts over the computations ``within`` (default: all but the
    bodies of fusions, whose values live in registers): see the module
    docstring.  ``item_dims`` one group's ``[b, kv, L, hd]``;
    ``buffer_dims`` the whole buffer's where it has groups."""
    item = _dims(item_dims)
    whole = _dims(buffer_dims) if buffer_dims is not None else None
    fused = _fusion_bodies(comps)
    row_writes, prefetches, items, buffers = 0, 0, [], []
    for name, lines in comps.items():
        if name in fused or (within is not None and name not in within):
            continue
        for line in lines:
            m = _INSTR.match(line)
            if not m:
                # of a call's several results, those that alias an
                # operand are written where it lies
                call = _CALL.match(line)
                if call and "output_to_operand_aliasing" in call[2]:
                    # ``{{1}: (5, {}), {2}: (6, {})}``: result -> operand
                    aliased = {int(i) for i in
                               re.findall(r"\{(\d+)\}: \(", call[2])}
                    shapes = re.findall(r"\w+\[([\d,]*)\]", call[1])
                    row_writes += sum(
                        i in aliased and bool(dims)
                        and _dims(dims.split(",")) in (item, whole)
                        for i, dims in enumerate(shapes))
                continue
            op_name, _dtype, dims, opcode, rest = m.groups()
            if opcode in _FREE or not dims:
                continue
            got = _dims(dims.split(","))
            if got != item and got != whole:
                continue
            if opcode == "fusion":
                called = re.search(r"calls=%?([\w.\-]+)", rest)
                opcode = _root_opcode(comps.get(called.group(1), [])) \
                    if called else opcode
            in_place = opcode == "dynamic-update-slice" or (
                opcode == "custom-call"
                and "output_to_operand_aliasing" in rest)
            if in_place:
                row_writes += 1
            elif opcode in _ASYNC or "ConcatBitcast" in rest:
                prefetches += 1
            else:
                (buffers if got == whole else items).append(op_name)

    def kinds(names):
        return sorted({_kind(n) for n in names})

    return {"row_writes": row_writes, "item_copies": len(items),
            "item_copy_kinds": kinds(items),
            "buffer_copies": len(buffers),
            "buffer_copy_kinds": kinds(buffers),
            "item_prefetches": prefetches}


def weight_copies(comps: dict[str, list[str]], matrices) -> dict:
    """The instructions, fusions' bodies and prefetches aside, that
    *produce* an array of the size of one of ``matrices`` (shapes): a
    leaf cut out of a flat row of weights is laid out anew inside the
    loop, every step; one handed over as an argument of its own, as the
    ring hands every leaf, is only read.  ``in_loop`` counts those of the loop's
    computations, ``per_dispatch`` the entry computation's (a layout
    the loop wants otherwise than the caller holds it, converted once)."""
    sizes = {tuple(_dims(shape)) for shape in matrices}
    fused = _fusion_bodies(comps)
    made = {"ENTRY": [], "loop": []}
    for name, lines in comps.items():
        if name in fused:
            continue
        for m in filter(None, map(_INSTR.match, lines)):
            op_name, _dtype, dims, opcode, rest = m.groups()
            if opcode in _FREE or opcode in _ASYNC or "ConcatBitcast" in rest \
                    or not dims or tuple(_dims(dims.split(","))) not in sizes:
                continue
            made["ENTRY" if name == "ENTRY" else "loop"].append(
                _kind(op_name))
    return {"weight_copies_in_loop": len(made["loop"]),
            "weight_copy_kinds_in_loop": sorted(set(made["loop"])),
            "weight_copies_per_dispatch": len(made["ENTRY"])}


def grouped_products(text: str) -> dict:
    """How a compiled program runs its routed experts' grouped products:
    calls of the two kernels of ``defer_tpu/ops/grouped.py`` (a step's
    ``grouped_experts``, a prompt's ``grouped_rows``) and
    ``lax.ragged_dot``'s (which the TPU compiler turns into a call of
    its own, ``%ragged-dot-none.7 = ... custom-call(``; since PR 56 no
    program of the package holds one)."""
    return {"grouped_experts_calls": len(re.findall(
                r"%grouped_experts[.\d]* = .*tpu_custom_call", text)),
            "grouped_rows_calls": len(re.findall(
                r"%grouped_rows[.\d]* = .*tpu_custom_call", text)),
            "ragged_dots": len(re.findall(
                r"%ragged-dot[\w.\-]* = \S+ custom-call\(", text))}


class GroupedCounters:
    """The shape rule's counters over a ``with`` block (a program's
    lowering): ``.read`` holds what the block added to
    ``moe.grouped.kernel_products`` / ``.tiled_products``."""

    NAMES = ("moe.grouped.kernel_products", "moe.grouped.tiled_products")

    def _now(self) -> list[int]:
        from defer_tpu.obs import REGISTRY
        return [REGISTRY.counter(name).value for name in self.NAMES]

    def __enter__(self):
        self._before = self._now()
        self.read: dict = {}
        return self

    def __exit__(self, *exc):
        self.read = {name: after - before for name, before, after
                     in zip(self.NAMES, self._before, self._now())}
