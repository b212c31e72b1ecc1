"""sha256 of ``kv_attend_joined`` (``defer_tpu/ops/kv_cache.py``) as it
lowers for the chip at the shapes of the six cells that call it, to
show that a change to the kernel left a cell's call what it was — no
chip needed, not part of the tests.  ``scripts/lowered_text_hashes.py``
cannot say: no tiny family's heads are a lane row wide, so none holds
joined rows.

    env JAX_PLATFORMS=cpu python scripts/joined_lowering_hashes.py [DIR]

Run it in two trees and compare the lines: a call a line — Mellum2's
and command-a-plus's full and window layers (4 x 8 and 8 x 16 queries on
heads of 128, 16 sequences), Jamba's (1 x 20, 256 sequences) and, since
PR 64, granite's (8 x 4, 64 sequences: a tree from before it lowers the
same call, which its format did not yet make) and, since PR 66, LFM2's
(8 x 4 on heads of 64, two a lane row, 128 sequences: a tree from
before it lowers another kernel there, slices of 64 columns, which no
format made) and, since PR 67, Nemotron-3-Super's (2 x 16 on heads of
128: rows of 512 B a position, 128 sequences; the kernel is older
trees' too, no format of theirs made the call) and, since PR 68, GPT-2's
(26 x 1 on heads of 64, 25 and a phantom: one query a head, so the whole
row's heads side by side are one head of 1664 columns and 26 query
rows; 8 sequences a group, and the four-chip ring's 2; a tree from
before it lowers 13 lane-row heads of two query rows there, which no
format made) — over bfloat16 buffers as the cells hold them — with the
hash of the Mosaic
kernel's text and of the text around it.  The kernel's body travels as
bytecode that carries its source lines; it is hashed as text without
them.  With ``DIR`` both texts are written there for ``diff``.
"""

import base64
import functools
import hashlib
import json
import os
import re
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
from jax._src.interpreters import mlir
from jax._src.lib import tpu
from jax._src.lib.mlir import ir
from jax.experimental import topologies
from jax.sharding import SingleDeviceSharding

from defer_tpu.ops import kv_cache

#: name: (KV heads, queries a head, sequences, the buffers' rows[, a
#: head's width where it is not 128])
CALLS = {
    "mellum2.full": (4, 8, 16, 28688),
    "mellum2.window": (4, 8, 16, 1040),
    "commandaplus.full": (8, 16, 16, 12304),
    "commandaplus.window": (8, 16, 16, 4112),
    "jamba2": (1, 20, 256, 4368),
    "granite4h": (8, 4, 64, 3088),
    "lfm2moe": (8, 4, 128, 2560, 64),
    "nemotron3super": (2, 16, 128, 3600),
    "gpt2xl": (26, 1, 8, 784, 64),
    "gpt2xl.pipe4": (26, 1, 2, 784, 64),
}


def kernel_text(lowered: str) -> tuple[str, str]:
    """``(the Mosaic module as text without locations, the rest)``."""
    config = re.search(r'backend_config = "(\{.*?\})"', lowered).group(1)
    body = json.loads(config.replace("\\22", '"'))["custom_call_config"]["body"]
    ctx = mlir.make_ir_context()
    tpu.register_dialect(ctx)
    ctx.allow_unregistered_dialects = True      # the versioned dialect
    with ctx:
        module = ir.Module.parse(base64.b64decode(body))
        return (module.operation.get_asm(enable_debug_info=False),
                lowered.replace(config, ""))


def main(out: str | None = None) -> int:
    chip = SingleDeviceSharding(topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0])
    # the call asks the backend whether to interpret: lower the kernel
    jax.default_backend = lambda: "tpu"
    if out:
        os.makedirs(out, exist_ok=True)
    for name, (kv, g, b, rows, *hd) in CALLS.items():
        hd = hd[0] if hd else 128

        def arg(shape, dtype=jnp.bfloat16):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

        buf = arg((2, b, rows, kv * hd))
        lowered = jax.jit(functools.partial(
            kv_cache.kv_attend_joined.__wrapped__, kv=kv)).lower(
                arg((b, kv * g * hd)), buf, buf, arg((b,), jnp.int32),
                arg((1,), jnp.int32)).as_text(debug_info=False)
        kernel, around = kernel_text(lowered)
        print(name, *(hashlib.sha256(t.encode()).hexdigest()
                      for t in (kernel, around)), len(kernel))
        if out:
            for kind, text in (("kernel", kernel), ("around", around)):
                with open(os.path.join(out, f"{name}.{kind}.txt"), "w") as f:
                    f.write(text)
    return 0


if __name__ == "__main__":
    sys.exit(main(*sys.argv[1:2]))
