"""Layer op library (NHWC, MXU-friendly).

These replace the Keras layer zoo the reference leans on (its compute is
entirely ``model.predict`` — reference src/node.py:106).  Conventions:

  * NHWC activations / HWIO kernels — the TPU-native conv layout.
  * Parameters are created in float32; ``apply`` computes in the incoming
    activation dtype (cast params down), so running the pipeline in bfloat16
    keeps the MXU fed without separate model definitions.
  * BatchNorm is inference-mode (folded running stats), matching DEFER's
    inference-only scope.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Sequence

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from ..ops.routed import expert_dispatch, route_top_k
from .ir import Op, ShapeSpec


def _cast(p, dtype):
    return jax.tree.map(lambda a: a.astype(dtype), p)


def _sym_pad(padding, *, nhwc: bool = False):
    """Normalize a padding spec: "SAME"/"VALID" pass through; an explicit
    symmetric ``(ph, pw)`` becomes lax pad pairs (spatial-only, or padded
    out to NHWC rank for reduce_window).  The ONE place the convention
    lives — conv, depthwise, and pooling all route through it."""
    if isinstance(padding, str):
        return padding
    ph, pw = padding
    pairs = ((ph, ph), (pw, pw))
    return ((0, 0), *pairs, (0, 0)) if nhwc else list(pairs)


# ---------------------------------------------------------------------------
# dense / conv
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True, repr=False)
class Dense(Op):
    features: int
    use_bias: bool = True

    def init(self, key, in_specs):
        (spec,) = in_specs
        d = spec.shape[-1]
        wkey, _ = jax.random.split(key)
        scale = 1.0 / math.sqrt(d)
        p = {"w": jax.random.uniform(wkey, (d, self.features), jnp.float32,
                                     -scale, scale)}
        if self.use_bias:
            p["b"] = jnp.zeros((self.features,), jnp.float32)
        return p

    def apply(self, params, x):
        p = _cast(params, x.dtype)
        y = x @ p["w"]
        if self.use_bias:
            y = y + p["b"]
        return y

    def flops(self, in_specs, out_spec):
        (spec,) = in_specs
        return 2 * spec.size * self.features

    # -- tensor parallelism: row-parallel (input dim sharded, one psum) ----

    def tp_shard(self, params, tp, rank):
        w = params["w"]
        d = w.shape[0]
        if d % tp:
            raise ValueError(f"Dense input dim {d} not divisible by tp={tp}")
        blk = d // tp
        out = {"w": w[rank * blk:(rank + 1) * blk]}
        if self.use_bias:
            out["b"] = params["b"]  # replicated; added once after the psum
        return out

    def tp_apply(self, params, x, *, axis_name=None, tp=1):
        if axis_name is None or tp == 1:
            return self.apply(params, x)
        p = _cast(params, x.dtype)
        blk = p["w"].shape[0]
        idx = lax.axis_index(axis_name)
        xs = lax.dynamic_slice_in_dim(x, idx * blk, blk, axis=x.ndim - 1)
        y = lax.psum(xs @ p["w"], axis_name)
        if self.use_bias:
            y = y + p["b"]
        return y

    def tp_unshard(self, shards):
        out = {"w": jnp.concatenate([s["w"] for s in shards], axis=0)}
        if self.use_bias:
            out["b"] = shards[0]["b"]  # replicated
        return out


@dataclasses.dataclass(frozen=True, repr=False)
class Conv2D(Op):
    features: int
    kernel: int | tuple[int, int] = 3
    stride: int | tuple[int, int] = 1
    #: "SAME"/"VALID", or an explicit symmetric (ph, pw) pad.  The tuple
    #: form exists for torch-trained weights: torch pads stride-2 convs
    #: symmetrically (k//2 each side) where XLA SAME pads (0, 1)-style
    #: asymmetrically — numerically different at every downsampling conv.
    padding: str | tuple[int, int] = "SAME"
    use_bias: bool = True
    groups: int = 1

    def _k(self):
        k = self.kernel
        return (k, k) if isinstance(k, int) else tuple(k)

    def _s(self):
        s = self.stride
        return (s, s) if isinstance(s, int) else tuple(s)

    def _p(self):
        return _sym_pad(self.padding)

    def init(self, key, in_specs):
        (spec,) = in_specs
        kh, kw = self._k()
        cin = spec.shape[-1]
        fan_in = kh * kw * cin // self.groups
        wkey, _ = jax.random.split(key)
        p = {"w": jax.random.normal(wkey, (kh, kw, cin // self.groups,
                                           self.features), jnp.float32)
             * math.sqrt(2.0 / fan_in)}
        if self.use_bias:
            p["b"] = jnp.zeros((self.features,), jnp.float32)
        return p

    def apply(self, params, x):
        p = _cast(params, x.dtype)
        y = lax.conv_general_dilated(
            x, p["w"], window_strides=self._s(), padding=self._p(),
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            feature_group_count=self.groups,
        )
        if self.use_bias:
            y = y + p["b"]
        return y

    def flops(self, in_specs, out_spec):
        (spec,) = in_specs
        kh, kw = self._k()
        cin = spec.shape[-1]
        return 2 * out_spec.size * kh * kw * cin // self.groups


@dataclasses.dataclass(frozen=True, repr=False)
class DepthwiseConv2D(Op):
    kernel: int = 3
    stride: int = 1
    #: "SAME"/"VALID" or explicit symmetric (ph, pw) — see Conv2D.padding
    padding: str | tuple[int, int] = "SAME"
    use_bias: bool = False  # enabled by the BatchNorm-folding pass

    def init(self, key, in_specs):
        (spec,) = in_specs
        c = spec.shape[-1]
        k = self.kernel
        p = {"w": jax.random.normal(key, (k, k, 1, c), jnp.float32)
             * math.sqrt(2.0 / (k * k))}
        if self.use_bias:
            p["b"] = jnp.zeros((c,), jnp.float32)
        return p

    def apply(self, params, x):
        p = _cast(params, x.dtype)
        c = x.shape[-1]
        y = lax.conv_general_dilated(
            x, p["w"], window_strides=(self.stride, self.stride),
            padding=_sym_pad(self.padding),
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            feature_group_count=c,
        )
        if self.use_bias:
            y = y + p["b"]
        return y

    def flops(self, in_specs, out_spec):
        return 2 * out_spec.size * self.kernel * self.kernel


# ---------------------------------------------------------------------------
# normalization
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True, repr=False)
class BatchNorm(Op):
    """Inference-mode batch norm (running statistics folded at apply)."""

    eps: float = 1e-5

    def init(self, key, in_specs):
        del key
        (spec,) = in_specs
        c = spec.shape[-1]
        return {
            "scale": jnp.ones((c,), jnp.float32),
            "bias": jnp.zeros((c,), jnp.float32),
            "mean": jnp.zeros((c,), jnp.float32),
            "var": jnp.ones((c,), jnp.float32),
        }

    def apply(self, params, x):
        p = _cast(params, x.dtype)
        inv = lax.rsqrt(p["var"] + jnp.asarray(self.eps, x.dtype))
        return (x - p["mean"]) * (inv * p["scale"]) + p["bias"]


@dataclasses.dataclass(frozen=True, repr=False)
class LayerNorm(Op):
    eps: float = 1e-6

    def init(self, key, in_specs):
        del key
        (spec,) = in_specs
        d = spec.shape[-1]
        return {"scale": jnp.ones((d,), jnp.float32),
                "bias": jnp.zeros((d,), jnp.float32)}

    def apply(self, params, x):
        p = _cast(params, x.dtype)
        mu = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
        return (x - mu) * lax.rsqrt(var + jnp.asarray(self.eps, x.dtype)) \
            * p["scale"] + p["bias"]


def rms_norm(x, scale, eps):
    """``x / rms(x) * scale`` over the last axis, statistics in float32."""
    xf = x.astype(jnp.float32)
    y = xf * lax.rsqrt(jnp.mean(jnp.square(xf), axis=-1, keepdims=True) + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


@dataclasses.dataclass(frozen=True, repr=False)
class RMSNorm(Op):
    eps: float = 1e-5

    def init(self, key, in_specs):
        del key
        (spec,) = in_specs
        return {"scale": jnp.ones((spec.shape[-1],), jnp.float32)}

    def apply(self, params, x):
        return rms_norm(x, params["scale"], self.eps)


# ---------------------------------------------------------------------------
# activations / pooling / structural
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True, repr=False)
class Activation(Op):
    kind: str = "relu"  # relu | relu6 | gelu | swish | softmax | tanh

    def apply(self, params, x):
        del params
        if self.kind == "relu":
            return jax.nn.relu(x)
        if self.kind == "relu6":
            return jnp.minimum(jax.nn.relu(x), jnp.asarray(6, x.dtype))
        if self.kind == "gelu":
            return jax.nn.gelu(x)
        if self.kind == "swish":
            return jax.nn.swish(x)
        if self.kind == "softmax":
            return jax.nn.softmax(x, axis=-1)
        if self.kind == "tanh":
            return jnp.tanh(x)
        raise ValueError(self.kind)


@dataclasses.dataclass(frozen=True, repr=False)
class MaxPool(Op):
    window: int = 2
    stride: int | None = None
    #: "SAME"/"VALID" or explicit symmetric (ph, pw) — see Conv2D.padding
    padding: str | tuple[int, int] = "VALID"

    def apply(self, params, x):
        del params
        s = self.stride or self.window
        if jnp.issubdtype(x.dtype, jnp.floating):
            identity = -jnp.inf
        else:
            identity = jnp.iinfo(x.dtype).min
        return lax.reduce_window(
            x, identity, lax.max,
            (1, self.window, self.window, 1), (1, s, s, 1),
            _sym_pad(self.padding, nhwc=True))


@functools.lru_cache(maxsize=256)
def _window_counts(hw: tuple[int, int], window: int, stride: int,
                   padding: str) -> np.ndarray:
    """[1, H', W', 1] valid-element count per pooling window (XLA SAME/
    VALID semantics), as a host-side constant."""
    h, w = hw
    padding = padding.upper()  # lax accepts lowercase padding strings
    if padding == "VALID":
        oh = (h - window) // stride + 1
        ow = (w - window) // stride + 1
        return np.full((1, oh, ow, 1), float(window * window), np.float32)
    oh, ow = -(-h // stride), -(-w // stride)
    ph = max((oh - 1) * stride + window - h, 0)
    pw = max((ow - 1) * stride + window - w, 0)
    mask = np.zeros((h + ph, w + pw), np.float32)
    mask[ph // 2: ph // 2 + h, pw // 2: pw // 2 + w] = 1.0
    out = np.empty((oh, ow), np.float32)
    for i in range(oh):
        for j in range(ow):
            out[i, j] = mask[i * stride: i * stride + window,
                             j * stride: j * stride + window].sum()
    return out.reshape(1, oh, ow, 1)


@dataclasses.dataclass(frozen=True, repr=False)
class AvgPool(Op):
    window: int = 2
    stride: int | None = None
    padding: str = "VALID"
    #: True = divide by window**2 even where the window overlaps padding
    #: (torch ``avg_pool2d``'s default, used by torchvision InceptionV3's
    #: pool branches); False = divide by the valid-element count (XLA/
    #: Keras semantics).
    count_include_pad: bool = False

    def apply(self, params, x):
        del params
        s = self.stride or self.window
        # NOTE the init value must be a python scalar LITERAL: an array
        # init routes to the generic reduce_window primitive, whose remat
        # linearization fails under jax.grad(jax.checkpoint(...)) — the
        # literal routes to the dedicated (transposable) sum primitive
        summed = lax.reduce_window(x, 0.0, lax.add,
                                   (1, self.window, self.window, 1),
                                   (1, s, s, 1), self.padding)
        if self.count_include_pad:
            return summed / jnp.asarray(self.window * self.window, x.dtype)
        # window counts depend only on static shape/padding: bake them in
        # as a numpy constant
        counts = _window_counts(x.shape[1:3], self.window, s, self.padding)
        return summed / jnp.asarray(counts, x.dtype)


@dataclasses.dataclass(frozen=True, repr=False)
class GlobalAvgPool(Op):
    def apply(self, params, x):
        del params
        return jnp.mean(x, axis=(1, 2))


@dataclasses.dataclass(frozen=True, repr=False)
class ZeroPad2D(Op):
    pad: int = 1

    def apply(self, params, x):
        del params
        p = self.pad
        return jnp.pad(x, ((0, 0), (p, p), (p, p), (0, 0)))


@dataclasses.dataclass(frozen=True, repr=False)
class Add(Op):
    """Residual merge — DEFER's canonical cut-point layer (its ResNet50
    benchmark cuts only at ``add_*`` layers, reference test/test.py:18)."""

    def apply(self, params, *xs):
        del params
        y = xs[0]
        for x in xs[1:]:
            y = y + x
        return y


@dataclasses.dataclass(frozen=True, repr=False)
class Concat(Op):
    axis: int = -1

    def apply(self, params, *xs):
        del params
        return jnp.concatenate(xs, axis=self.axis)


@dataclasses.dataclass(frozen=True, repr=False)
class Flatten(Op):
    def apply(self, params, x):
        del params
        return x.reshape(x.shape[0], -1)


@dataclasses.dataclass(frozen=True, repr=False)
class Tile(Op):
    """Repeat the per-sample input ``reps`` times along a new leading
    axis — a cheap FAT-activation producer (output bytes = reps x input
    bytes for one broadcast write).  Bench models for copy-bound
    transport work (``scripts/ici_smoke.py``) use it to make a boundary
    tensor fat without making the compute expensive."""

    reps: int = 2

    def apply(self, params, x):
        del params
        return jnp.broadcast_to(
            x[:, None, ...], (x.shape[0], self.reps) + x.shape[1:])


@dataclasses.dataclass(frozen=True, repr=False)
class Cast(Op):
    """Element dtype cast (e.g. to ``bfloat16`` — the TPU-native
    activation regime, where a host round-trip pays a real
    materialization the device-resident path skips)."""

    dtype: str = "bfloat16"

    def apply(self, params, x):
        del params
        return x.astype(self.dtype)


@dataclasses.dataclass(frozen=True, repr=False)
class ReduceMean(Op):
    """Mean over one per-sample axis — the matching fat-activation
    consumer (one read pass, thin output)."""

    axis: int = 1

    def apply(self, params, x):
        del params
        return jnp.mean(x, axis=self.axis)

    def flops(self, in_specs, out_spec):
        (spec,) = in_specs
        return spec.size  # one add per reduced element


@dataclasses.dataclass(frozen=True, repr=False)
class Embedding(Op):
    vocab: int
    features: int

    def init(self, key, in_specs):
        del in_specs
        return {"table": jax.random.normal(key, (self.vocab, self.features),
                                           jnp.float32) * 0.02}

    def apply(self, params, x):
        return params["table"].astype(jnp.float32)[x.astype(jnp.int32)]


# ---------------------------------------------------------------------------
# transformer block (one node per block ⇒ natural BERT cut points)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True, repr=False)
class TransformerBlock(Op):
    """Pre-LN transformer encoder block as a single graph node.

    Modeling each block as one node mirrors how the BERT-Base/12 baseline
    config places one block per pipeline stage (BASELINE.md config 5); every
    block output is automatically a valid single-tensor cut point.
    """

    num_heads: int
    mlp_ratio: int = 4
    #: "auto" = Pallas flash attention on TPU / plain XLA elsewhere;
    #: "flash" and "xla" force one implementation
    attn_impl: str = "auto"
    #: "pre" (GPT-style: x + f(LN(x))) or "post" (original-BERT style:
    #: LN(x + f(x))) — post is required for faithful import of HF BERT
    #: checkpoints, whose weights were trained under post-LN residuals
    norm: str = "pre"
    ln_eps: float = 1e-6

    def __post_init__(self):
        if self.norm not in ("pre", "post"):  # one check covers BOTH the
            # plain and the tensor-parallel forward paths
            raise ValueError(
                f"norm must be 'pre' or 'post', got {self.norm!r}")

    def init(self, key, in_specs):
        (spec,) = in_specs
        d = spec.shape[-1]
        h = self.mlp_ratio * d
        ks = jax.random.split(key, 6)
        s = 1.0 / math.sqrt(d)
        return {
            "ln1": {"scale": jnp.ones((d,), jnp.float32),
                    "bias": jnp.zeros((d,), jnp.float32)},
            "qkv": {"w": jax.random.normal(ks[0], (d, 3 * d), jnp.float32) * s,
                    "b": jnp.zeros((3 * d,), jnp.float32)},
            "proj": {"w": jax.random.normal(ks[1], (d, d), jnp.float32) * s,
                     "b": jnp.zeros((d,), jnp.float32)},
            "ln2": {"scale": jnp.ones((d,), jnp.float32),
                    "bias": jnp.zeros((d,), jnp.float32)},
            "fc1": {"w": jax.random.normal(ks[2], (d, h), jnp.float32) * s,
                    "b": jnp.zeros((h,), jnp.float32)},
            "fc2": {"w": jax.random.normal(ks[3], (h, d), jnp.float32)
                    * (1.0 / math.sqrt(h)),
                    "b": jnp.zeros((d,), jnp.float32)},
        }

    @staticmethod
    def _ln(p, x, eps=1e-6):
        mu = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
        return (x - mu) * lax.rsqrt(var + jnp.asarray(eps, x.dtype)) \
            * p["scale"] + p["bias"]

    def _attend(self, q, k, v):
        """Scaled-dot-product attention on [b, nh, t, hd] (impl dispatch)."""
        impl = self.attn_impl
        if impl == "auto":
            impl = "flash" if jax.default_backend() == "tpu" else "xla"
        if impl not in ("flash", "xla"):
            raise ValueError(
                f"attn_impl must be 'auto', 'flash' or 'xla', got {impl!r}")
        if impl == "flash":
            from ..ops import flash_attention
            return flash_attention(q, k, v)
        hd = q.shape[-1]
        att = jnp.einsum("bhqd,bhkd->bhqk", q, k) / math.sqrt(hd)
        att = jax.nn.softmax(att, axis=-1)
        return jnp.einsum("bhqk,bhkd->bhqd", att, v)

    def _attend_columns(self, q, k, v):
        """Attention on the projection's own columns — ``q`` [b, t,
        nh*hd], ``k`` / ``v`` [b, t, kv*hd] — with the heads merged
        again, [b, t, nh*hd]: the heads split out, :meth:`_attend`."""
        b, t, d = q.shape
        nh, kvh = self.num_heads, self._kv_head_count()
        hd = d // nh
        qh = q.reshape(b, t, nh, hd).transpose(0, 2, 1, 3)
        kh = k.reshape(b, t, kvh, hd).transpose(0, 2, 1, 3)
        vh = v.reshape(b, t, kvh, hd).transpose(0, 2, 1, 3)
        if kvh != nh:
            # broadcast each KV head over its query group (exact GQA)
            kh = jnp.repeat(kh, nh // kvh, axis=1)
            vh = jnp.repeat(vh, nh // kvh, axis=1)
        y = self._attend(qh, kh, vh)
        return y.transpose(0, 2, 1, 3).reshape(b, t, d)

    def _split_qkv(self, qkv):
        """q/k/v column split of the fused projection (subclass hook)."""
        return jnp.split(qkv, 3, axis=-1)

    def _kv_head_count(self) -> int:
        """KV head count (subclass hook; GQA blocks return fewer)."""
        return self.num_heads

    def apply(self, params, x):
        return self.apply_with_kv(params, x)[0]

    def apply_with_kv(self, params, x):
        """Forward that also returns the raw K/V projections.

        The single definition of the block forward — ``apply`` discards the
        byproducts (XLA dead-code-eliminates them); decode-cache seeding
        (the decode ring's prefill) consumes them.  K/V are [b, t, kv*hd]
        pre-head-split columns (kv == num_heads unless a GQA subclass
        narrows them).
        """
        p = _cast(params, x.dtype)
        eps = self.ln_eps
        post = self.norm == "post"  # validated in __post_init__

        y = x if post else self._ln(p["ln1"], x, eps)
        qkv = y @ p["qkv"]["w"] + p["qkv"]["b"]
        q, k, v = self._split_qkv(qkv)
        y = self._attend_columns(q, k, v)
        y = y @ p["proj"]["w"] + p["proj"]["b"]
        x = self._ln(p["ln1"], x + y, eps) if post else x + y

        y = x if post else self._ln(p["ln2"], x, eps)
        # post-LN (BERT) uses the exact erf GELU like HF; pre-LN keeps
        # the tanh approximation (GPT-2 convention, existing behavior)
        y = jax.nn.gelu(y @ p["fc1"]["w"] + p["fc1"]["b"],
                        approximate=not post)
        y = y @ p["fc2"]["w"] + p["fc2"]["b"]
        out = self._ln(p["ln2"], x + y, eps) if post else x + y
        return out, k, v

    def flops(self, in_specs, out_spec):
        (spec,) = in_specs
        t, d = spec.shape
        return 2 * t * d * (4 * d + 2 * self.mlp_ratio * d) + 4 * t * t * d

    # -- tensor parallelism: Megatron column->row pairing, heads sharded ---

    def tp_shard(self, params, tp, rank):
        nh, kv = self.num_heads, self._kv_head_count()
        if nh % tp or kv % tp:
            raise ValueError(
                f"heads={nh}/kv_heads={kv} not divisible by tp={tp} "
                f"(each rank must hold whole query groups)")
        d = params["qkv"]["w"].shape[0]
        hd = d // nh
        blk = d // tp                 # query columns per rank
        kvblk = (kv // tp) * hd       # K (and V) columns per rank
        # fused layout: [q (nh*hd) | k (kv*hd) | v (kv*hd)]; kv == nh
        # reduces to the classic Megatron equal-thirds slice
        q0, k0, v0 = 0, d, d + kv * hd

        def qkv_cols(a):
            # per-chunk column slice so each rank gets whole (query) heads
            return jnp.concatenate(
                [a[..., q0 + rank * blk: q0 + (rank + 1) * blk],
                 a[..., k0 + rank * kvblk: k0 + (rank + 1) * kvblk],
                 a[..., v0 + rank * kvblk: v0 + (rank + 1) * kvblk]],
                axis=-1)

        return {
            "qkv": {"w": qkv_cols(params["qkv"]["w"]),
                    "b": qkv_cols(params["qkv"]["b"])},
            **self._tp_shard_common(params, tp, rank),
        }

    def _tp_shard_common(self, params, tp, rank):
        """The non-qkv Megatron shards (LNs replicated, proj rows, MLP
        column->row pair) — shared by the MHA and GQA qkv schemes."""
        d = params["qkv"]["w"].shape[0]
        h = params["fc1"]["w"].shape[1]
        if h % tp:
            raise ValueError(f"mlp width {h} not divisible by tp={tp}")
        blk, hblk = d // tp, h // tp
        return {
            "ln1": params["ln1"],
            "proj": {"w": params["proj"]["w"][rank * blk:(rank + 1) * blk],
                     "b": params["proj"]["b"]},
            "ln2": params["ln2"],
            "fc1": {"w": params["fc1"]["w"][:, rank * hblk:(rank + 1) * hblk],
                    "b": params["fc1"]["b"][rank * hblk:(rank + 1) * hblk]},
            "fc2": {"w": params["fc2"]["w"][rank * hblk:(rank + 1) * hblk],
                    "b": params["fc2"]["b"]},
        }

    def tp_unshard(self, shards):
        """Inverse of :meth:`tp_shard`: concatenate each rank's query/K/V
        column groups back into the fused layout, proj/fc2 rows and fc1
        columns back to full width; LNs and biases are replicated."""
        tp = len(shards)
        nh, kv = self.num_heads, self._kv_head_count()
        d = shards[0]["proj"]["w"].shape[1]
        hd = d // nh
        blk, kvblk = d // tp, (kv // tp) * hd

        def qkv_cat(key):
            qs, ks, vs = [], [], []
            for sh in shards:
                a = sh["qkv"][key]
                qs.append(a[..., :blk])
                ks.append(a[..., blk: blk + kvblk])
                vs.append(a[..., blk + kvblk:])
            return jnp.concatenate(qs + ks + vs, axis=-1)

        return {
            "ln1": shards[0]["ln1"],
            "qkv": {"w": qkv_cat("w"), "b": qkv_cat("b")},
            "proj": {"w": jnp.concatenate(
                [sh["proj"]["w"] for sh in shards], axis=0),
                "b": shards[0]["proj"]["b"]},
            "ln2": shards[0]["ln2"],
            "fc1": {"w": jnp.concatenate(
                [sh["fc1"]["w"] for sh in shards], axis=1),
                "b": jnp.concatenate(
                    [sh["fc1"]["b"] for sh in shards], axis=0)},
            "fc2": {"w": jnp.concatenate(
                [sh["fc2"]["w"] for sh in shards], axis=0),
                "b": shards[0]["fc2"]["b"]},
        }

    def tp_apply(self, params, x, *, axis_name=None, tp=1):
        if axis_name is None or tp == 1:
            return self.apply(params, x)
        p = _cast(params, x.dtype)
        b, t, d = x.shape
        nh = self.num_heads // tp           # local query heads
        kvl = self._kv_head_count() // tp   # local KV heads (GQA: fewer)
        hd = d // self.num_heads
        dl = nh * hd                        # local query width d/tp
        eps = self.ln_eps
        post = self.norm == "post"          # mirror apply_with_kv exactly

        y = x if post else self._ln(p["ln1"], x, eps)
        qkv = y @ p["qkv"]["w"] + p["qkv"]["b"]
        q = qkv[..., :dl]
        k = qkv[..., dl: dl + kvl * hd]
        v = qkv[..., dl + kvl * hd:]
        q = q.reshape(b, t, nh, hd).transpose(0, 2, 1, 3)
        k = k.reshape(b, t, kvl, hd).transpose(0, 2, 1, 3)
        v = v.reshape(b, t, kvl, hd).transpose(0, 2, 1, 3)
        if kvl != nh:
            # broadcast each local KV head over its query group
            k = jnp.repeat(k, nh // kvl, axis=1)
            v = jnp.repeat(v, nh // kvl, axis=1)
        y = self._attend(q, k, v)
        y = y.transpose(0, 2, 1, 3).reshape(b, t, dl)
        y = lax.psum(y @ p["proj"]["w"], axis_name) + p["proj"]["b"]
        x = self._ln(p["ln1"], x + y, eps) if post else x + y

        y = x if post else self._ln(p["ln2"], x, eps)
        y = jax.nn.gelu(y @ p["fc1"]["w"] + p["fc1"]["b"],
                        approximate=not post)
        y = lax.psum(y @ p["fc2"]["w"], axis_name) + p["fc2"]["b"]
        return self._ln(p["ln2"], x + y, eps) if post else x + y


# ---------------------------------------------------------------------------
# mixture of experts (expert parallelism rides parallel/expert.py)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True, repr=False)
class MoE(Op):
    """Switch-style top-1 mixture-of-experts FFN (with residual).

    Single-device ``apply`` groups rows by expert (:func:`expert_dispatch`
    at ``k`` = 1: one expert a token is computed); the expert-parallel
    path — experts sharded over an "expert" mesh axis with capacity-based
    ``all_to_all`` token dispatch — lives in
    :mod:`defer_tpu.parallel.expert` and is numerically identical
    whenever no token exceeds capacity.
    """

    num_experts: int
    hidden: int

    def init(self, key, in_specs):
        (spec,) = in_specs
        d = spec.shape[-1]
        e, h = self.num_experts, self.hidden
        ks = jax.random.split(key, 3)
        return {
            "gate": jax.random.normal(ks[0], (d, e), jnp.float32) * 0.02,
            "fc1": {"w": jax.random.normal(ks[1], (e, d, h), jnp.float32)
                    / math.sqrt(d),
                    "b": jnp.zeros((e, h), jnp.float32)},
            "fc2": {"w": jax.random.normal(ks[2], (e, h, d), jnp.float32)
                    / math.sqrt(h),
                    "b": jnp.zeros((e, d), jnp.float32)},
        }

    def route(self, params, x):
        """Top-1 routing: (expert_id [b,t], gate_prob [b,t])."""
        eid, pe = route_top_k(x @ params["gate"].astype(x.dtype), 1)
        return eid[..., 0], pe[..., 0].astype(x.dtype)

    def expert_fn(self, params, x, eid):
        """Run expert ``eid`` (array, broadcastable) on tokens ``x``.

        ``params`` holds stacked expert weights [E_local, ...]; ``eid``
        indexes into that local stack.
        """
        fc1 = params["fc1"]
        fc2 = params["fc2"]
        w1 = fc1["w"][eid].astype(x.dtype)
        b1 = fc1["b"][eid].astype(x.dtype)
        w2 = fc2["w"][eid].astype(x.dtype)
        b2 = fc2["b"][eid].astype(x.dtype)
        h = jax.nn.gelu(jnp.einsum("...d,...dh->...h", x, w1) + b1)
        return jnp.einsum("...h,...hd->...d", h, w2) + b2

    def apply(self, params, x):
        # the top_k=1, GELU, biased case of the one dispatch
        eid, pe = self.route(params, x)
        fc1, fc2 = _cast((params["fc1"], params["fc2"]), x.dtype)

        def experts(xs, sizes, es):
            h = jax.nn.gelu(lax.ragged_dot(xs, fc1["w"], sizes)
                            + fc1["b"][es])
            return lax.ragged_dot(h, fc2["w"], sizes) + fc2["b"][es]

        d = x.shape[-1]
        y, _ = expert_dispatch(x.reshape(-1, d), eid.reshape(-1, 1),
                               pe.reshape(-1, 1), self.num_experts, experts)
        return x + y.reshape(x.shape).astype(x.dtype)

    def flops(self, in_specs, out_spec):
        (spec,) = in_specs
        t, d = spec.shape
        # effective top-1 cost: one expert per token
        return 2 * t * d * (2 * self.hidden) + 2 * t * d * self.num_experts


@dataclasses.dataclass(frozen=True, repr=False)
class ExpertBranch(Op):
    """One expert's BRANCH of a branched mixture-of-experts layer.

    Where :class:`MoE` evaluates every expert inside one op (and the
    expert-parallel path shards them over a mesh axis,
    ``parallel/expert.py``), the branched formulation puts each expert
    on its own GRAPH branch so the DAG pipeline can place it on its own
    node: every branch reads the full block output (the fork tensor),
    computes its own softmax gate weight and expert FFN, and emits
    ``probs[..., expert] * ffn_e(x)``; the region's join is a plain
    :class:`Add` over the residual skip and all expert branches, so the
    merged output is the SOFT mixture ``x + sum_e p_e(x) * ffn_e(x)``.

    Soft (dense) gating on purpose: each branch re-derives its gate
    weight from its own replicated gate matrix, so branches stay
    self-contained single-input ops — a shared top-1 router would need a
    second tensor crossing the fork, which the single-tensor-cut
    transport does not carry.  Per-branch cost is one expert's FFN, the
    quantity expert-parallel placement divides.
    """

    num_experts: int
    expert: int
    hidden: int

    def init(self, key, in_specs):
        (spec,) = in_specs
        d = spec.shape[-1]
        ks = jax.random.split(key, 3)
        return {
            # the gate is replicated per branch and seeded by the
            # branch's OWN init key: gate weights differ across branches
            # by construction, which is fine for the soft mixture (each
            # branch's scalar weight is its own function of x)
            "gate": jax.random.normal(ks[0], (d, self.num_experts),
                                      jnp.float32) * 0.02,
            "fc1": {"w": jax.random.normal(ks[1], (d, self.hidden),
                                           jnp.float32) / math.sqrt(d),
                    "b": jnp.zeros((self.hidden,), jnp.float32)},
            "fc2": {"w": jax.random.normal(ks[2], (self.hidden, d),
                                           jnp.float32)
                    / math.sqrt(self.hidden),
                    "b": jnp.zeros((d,), jnp.float32)},
        }

    def apply(self, params, x):
        logits = x @ params["gate"].astype(x.dtype)
        pe = jax.nn.softmax(logits, axis=-1)[..., self.expert]
        h = jax.nn.gelu(x @ params["fc1"]["w"].astype(x.dtype)
                        + params["fc1"]["b"].astype(x.dtype))
        y = h @ params["fc2"]["w"].astype(x.dtype) \
            + params["fc2"]["b"].astype(x.dtype)
        return y * pe[..., None]

    def flops(self, in_specs, out_spec):
        (spec,) = in_specs
        t, d = spec.shape
        return 2 * t * d * (2 * self.hidden) + 2 * t * d * self.num_experts
