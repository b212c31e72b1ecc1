"""sha256 of every tiny family's attention blocks lowered under
``attn_impl="flash"``, to show that a change to
``defer_tpu/ops/flash_attention.py`` left a family's prompt program
what it was — no chip needed, not part of the tests.
``scripts/lowered_text_hashes.py`` cannot say: it lowers on the CPU,
where ``"auto"`` means plain XLA, so no program of its holds a flash
kernel.

    env JAX_PLATFORMS=cpu python scripts/flash_lowering_hashes.py [DIR]

Run it in two trees (copy it into the older one) and compare the lines:
``family.node sha256 bytes entries`` (the jitted entries of
``ops/flash_attention.py`` the text calls), a line a block that names an
``attn_impl``, its full-sequence ``apply`` lowered on two sequences of
the family's own length with the Pallas kernels in interpreter mode (a
kernel's body is then part of the text).  With ``DIR`` each text is
also written to ``DIR/<family>.<node>.txt`` for ``diff``.
"""

import dataclasses
import hashlib
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

from defer_tpu import models

FAMILIES = ("gpt_tiny", "olmoe_tiny", "brumby_tiny", "cohere_moe_tiny",
            "jamba_tiny", "granite_hybrid_tiny", "kimi_k2_tiny",
            "mellum_tiny", "longcat_flash_tiny", "lfm2_moe_tiny",
            "solar_open2_tiny", "nemotron_h_tiny", "bert_tiny")


def main() -> int:
    out = sys.argv[1] if len(sys.argv) > 1 else None
    if out:
        os.makedirs(out, exist_ok=True)
    for family in FAMILIES:
        build = getattr(models, family, None)
        if build is None:       # a tree from before the family
            continue
        graph = build()
        seen = set()
        for name, node in graph.nodes.items():
            op = node.op
            if not hasattr(op, "attn_impl") or repr(vars(op)) in seen:
                continue
            seen.add(repr(vars(op)))    # a line a kind of block
            flash = dataclasses.replace(op, attn_impl="flash")
            (spec,) = (graph.out_spec(i) for i in node.inputs)
            text = jax.jit(flash.apply).lower(
                node.param_spec, spec.batched(2)).as_text()
            entries = sorted(set(re.findall(r"flash_[a-z_]+", text)))
            print(f"{family}.{name} "
                  f"{hashlib.sha256(text.encode()).hexdigest()} "
                  f"{len(text)} {','.join(entries) or '-'}", flush=True)
            if out:
                with open(os.path.join(out, f"{family}.{name}.txt"),
                          "w") as f:
                    f.write(text)
    return 0


if __name__ == "__main__":
    sys.exit(main())
