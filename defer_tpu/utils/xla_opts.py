"""XLA compiler options for the jit sites that matter.

Per-executable ``compiler_options`` (``jax.jit(..., compiler_options=)``)
reach the TPU compiler without going through the process-wide
``XLA_FLAGS`` parser, so a flag experiment can ride one environment
variable:

    DEFER_XLA_COMPILER_OPTS="xla_tpu_scoped_vmem_limit_kib=65536 \
        xla_tpu_enable_latency_hiding_scheduler=true" python bench.py

Space- or comma-separated ``key=value`` pairs; applied by the hot jit
sites (SpmdPipeline's stage program, bench's baseline forwards).  Unset
means exactly the default compile — the helper returns ``{}`` so call
sites can splat it unconditionally.  An option the platform's compiler
does not know fails the compile (``INVALID_ARGUMENT: No such compile
option``); the CPU client knows none of the TPU ones.
"""

from __future__ import annotations

import os


def compiler_options() -> dict[str, str]:
    """Parsed ``DEFER_XLA_COMPILER_OPTS`` (empty dict when unset)."""
    raw = os.environ.get("DEFER_XLA_COMPILER_OPTS", "").replace(",", " ")
    out: dict[str, str] = {}
    for tok in raw.split():
        if "=" not in tok:
            raise ValueError(
                f"DEFER_XLA_COMPILER_OPTS entry {tok!r} is not key=value")
        k, v = tok.split("=", 1)
        out[k] = v
    return out


def jit_kwargs() -> dict:
    """``{"compiler_options": {...}}`` or ``{}`` — splat into jax.jit."""
    opts = compiler_options()
    return {"compiler_options": opts} if opts else {}


#: the ring programs' TPU default: the stage->stage collective_permute
#: runs asynchronously, so the hop can overlap stage compute.  The
#: installed TPU compiler accepts it (libtpu 0.0.34, shown by
#: ``chip_smoke.py`` compiling every ring program with it); its effect
#: on throughput is not measured on the current installation.
RING_DEFAULTS = {"xla_enable_async_collective_permute": "true"}


def ring_jit_kwargs(devices) -> dict:
    """jit kwargs for ring (ppermute) programs: :data:`RING_DEFAULTS` on
    a TPU mesh, overridable key-by-key via ``DEFER_XLA_COMPILER_OPTS``
    (e.g. ``xla_enable_async_collective_permute=false``).  Any other
    platform gets only the explicit env options — only the TPU
    compiler is known to take the TPU flags.
    """
    first = devices.flat[0] if hasattr(devices, "flat") else devices[0]
    if getattr(first, "platform", None) != "tpu":
        return jit_kwargs()
    return {"compiler_options": {**RING_DEFAULTS, **compiler_options()}}
