"""Expert parallelism: MoE experts sharded over an ``"expert"`` mesh axis
with capacity-based ``lax.all_to_all`` token dispatch.

Absent from the reference (CNN pipelines only — SURVEY.md §2.3) but part of
this framework's first-class parallelism inventory.  The design is the
standard switch-routing EP pattern: tokens are data-sharded over the expert
axis, each device owns ``E / ep`` experts, and two ``all_to_all`` exchanges
over ICI move (token → owning expert) and (result → originating device).

Numerics match the dense single-device :meth:`MoE.apply` exactly whenever no
expert's per-device token count exceeds capacity; overflow tokens are
dropped (their FFN delta is zero, residual passes through) — switch-style
capacity semantics.
"""

from __future__ import annotations

import math
from typing import Any

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..graph.ops import MoE

EXPERT_AXIS = "expert"


def expert_parallel_mesh(ep: int, devices=None) -> Mesh:
    devices = list(devices if devices is not None else jax.devices())
    if len(devices) < ep:
        raise ValueError(f"need {ep} devices, have {len(devices)}")
    return Mesh(np.array(devices[:ep]), (EXPERT_AXIS,))


def shard_moe_params(op: MoE, params: dict[str, Any], ep: int,
                     mesh: Mesh | None = None, axis: str = EXPERT_AXIS):
    """Stack per-rank expert shards on a leading [ep, ...] axis.

    The gate is replicated (every device routes identically); fc1/fc2 are
    sliced so rank r owns experts [r*E/ep, (r+1)*E/ep).
    """
    e = op.num_experts
    if e % ep:
        raise ValueError(f"num_experts={e} not divisible by ep={ep}")
    el = e // ep

    def rank_shard(r):
        sl = slice(r * el, (r + 1) * el)
        return {
            "gate": params["gate"],
            "fc1": {"w": params["fc1"]["w"][sl], "b": params["fc1"]["b"][sl]},
            "fc2": {"w": params["fc2"]["w"][sl], "b": params["fc2"]["b"][sl]},
        }

    shards = [rank_shard(r) for r in range(ep)]
    out = jax.tree.map(
        lambda *xs: np.stack([np.asarray(x) for x in xs]), *shards)
    if mesh is not None:
        out = jax.device_put(out, NamedSharding(mesh, P(axis)))
    return out


def expert_parallel_apply(op: MoE, params_local, x, *, axis_name: str,
                          ep: int, capacity: int):
    """One EP MoE layer on this device's token shard ``x`` [b_local, t, d].

    ``params_local`` holds this rank's expert slice (leading axis already
    indexed away).  Two ``all_to_all``s: dispatch and return.
    """
    b, t, d = x.shape
    n = b * t
    el = op.num_experts // ep
    xf = x.reshape(n, d)

    eid, pe = op.route(params_local, x)
    eidf, pef = eid.reshape(n), pe.reshape(n).astype(xf.dtype)
    dest = eidf // el                                    # owning rank
    # slot = this token's arrival index within its dest's capacity buffer
    dmask = jax.nn.one_hot(dest, ep, dtype=jnp.int32)
    pos = (jnp.cumsum(dmask, axis=0) * dmask).sum(-1) - 1
    keep = pos < capacity
    slot = jnp.where(keep, pos, capacity)                # overflow -> C (cut)

    # payload = token features + its local expert index; the gate prob stays
    # local (applied to the returned result), so it never rides the wire.
    # The index rides in the activation dtype, so it must be exactly
    # representable there: floats are integer-exact only up to
    # 2**(mantissa+1) (bf16: 256, f16: 2048), beyond which routing would
    # silently send tokens to the wrong local expert.
    exact_max = 2 ** (jnp.finfo(xf.dtype).nmant + 1)
    if el > exact_max:
        raise ValueError(
            f"{el} local experts per device cannot ride an {xf.dtype} "
            f"all_to_all payload exactly (max {exact_max}); use wider "
            f"activations or more expert-parallel ranks")
    lid = (eidf % el).astype(xf.dtype)
    payload = jnp.concatenate([xf, lid[:, None]], axis=-1)  # [n, d+1]
    buf = jnp.zeros((ep, capacity + 1, d + 1), xf.dtype)
    buf = buf.at[dest, slot].set(payload)
    send = buf[:, :capacity]

    recv = lax.all_to_all(send, axis_name, split_axis=0, concat_axis=0)
    xr = recv[..., :d]                                   # [ep, C, d]
    lidr = recv[..., d].astype(jnp.int32)

    # masked dense sweep over my local experts (el is small by design; the
    # dispatch already cut tokens/device by ~ep)
    y = jnp.zeros_like(xr)
    for e in range(el):
        ye = op.expert_fn(params_local, xr, jnp.asarray(e))
        y = jnp.where((lidr == e)[..., None], ye, y)

    back = lax.all_to_all(y, axis_name, split_axis=0, concat_axis=0)
    y_tok = back[dest, jnp.clip(slot, 0, capacity - 1)]  # [n, d]
    y_tok = y_tok * keep[:, None].astype(xf.dtype) * pef[:, None]
    return x + y_tok.reshape(b, t, d)


def expert_parallel_fn(op: MoE, mesh: Mesh, axis: str = EXPERT_AXIS,
                       capacity_factor: float = 2.0,
                       tokens_per_device: int | None = None):
    """Jitted EP forward: ``fn(stacked_params, x) -> y``.

    ``x`` [B, t, d] is sharded on its batch dim over the expert axis;
    ``stacked_params`` comes from :func:`shard_moe_params`.  Capacity per
    device is ``ceil(capacity_factor * tokens_per_device / ep)`` (computed
    from the first call's shapes unless given explicitly).
    """
    ep = mesh.shape[axis]

    def local(pstk, x):
        p = jax.tree.map(lambda a: a[0], pstk)
        ntok = tokens_per_device or x.shape[0] * x.shape[1]
        cap = max(1, math.ceil(capacity_factor * ntok / ep))
        return expert_parallel_apply(op, p, x, axis_name=axis, ep=ep,
                                     capacity=cap)

    fn = jax.shard_map(local, mesh=mesh, in_specs=(P(axis), P(axis)),
                       out_specs=P(axis), check_vma=False)
    return jax.jit(fn)
