"""Stage program serialization: StableHLO + weights instead of Keras JSON.

The reference's control plane ships each partition to its node as Keras
architecture JSON plus compressed weights over TCP (reference
src/dispatcher.py:44-65, rebuilt via ``model_from_json`` at src/node.py:31).
The TPU-native equivalent serializes the *compiled artifact*: the stage's
jaxpr lowered through ``jax.export`` to portable StableHLO bytes, plus the
stage's weight pytree — loadable in a process that has no model code at
all, with XLA recompiling for the local device.  Useful for MPMD
deployments where stage hosts are separate processes, and as the durable
"partition artifact" format.
"""

from __future__ import annotations

import io
import json
import os
import zipfile
from typing import Any

import numpy as np

import jax
from jax import export as jax_export

from ..partition.stage import StageSpec

_MANIFEST = "manifest.json"
_PROGRAM = "stage.stablehlo"
_WEIGHTS = "weights.npz"


def stage_weight_leaves(stage: StageSpec,
                        params: dict[str, Any]) -> list[np.ndarray]:
    """The stage's weight pytree, flattened in the artifact's leaf order —
    the unit both full export and weights-only re-push ship."""
    leaves, _ = jax.tree.flatten(stage.select_params(params))
    return [np.asarray(l) for l in leaves]


def weights_blob(leaves: list[np.ndarray]) -> bytes:
    """npz-serialize a leaf list (the reweight payload)."""
    buf = io.BytesIO()
    np.savez(buf, **{f"w{i}": l for i, l in enumerate(leaves)})
    return buf.getvalue()


def _load_weights_blob(data: bytes, num: int) -> list[np.ndarray]:
    """Host arrays; :class:`StageProgram` places them on its device."""
    with np.load(io.BytesIO(data)) as npz:
        return [npz[f"w{i}"] for i in range(num)]


def export_stage_bytes(stage: StageSpec, params: dict[str, Any],
                       *, batch: int = 1) -> bytes:
    """Serialize one pipeline stage to zip-archive bytes.

    Contents: portable StableHLO of the stage function specialized to
    ``batch``, the stage's weight pytree, and a JSON manifest with shapes
    and stage metadata (the analogue of the arch-JSON + weights pair the
    reference ships per node, src/dispatcher.py:44-65) — a single blob so
    the dispatcher can ship it over the control connection.
    """
    sp = stage.select_params(params)
    leaves, treedef = jax.tree.flatten(sp)
    leaves = [np.asarray(l) for l in leaves]

    def fn(flat_leaves, *xs):
        p = jax.tree.unflatten(treedef, flat_leaves)
        return stage.fn(p, *xs)

    # a JoinStageSpec (branched pipelines, docs/TRANSPORT.md) takes P
    # boundary tensors — one per merged branch path, in path order
    in_specs = tuple(getattr(stage, "in_specs", None)
                     or (stage.in_spec,))
    x_specs = [jax.ShapeDtypeStruct((batch,) + s.shape, s.dtype)
               for s in in_specs]
    leaf_specs = [jax.ShapeDtypeStruct(l.shape, l.dtype) for l in leaves]
    exported = jax_export.export(jax.jit(fn))(leaf_specs, *x_specs)
    blob = exported.serialize()

    manifest = {
        "format": "defer_tpu.stage.v1",
        "index": stage.index,
        "name": stage.name,
        "graph": stage.graph.name,
        "input": getattr(stage, "input_name", None)
        or ",".join(stage.input_names),
        "output": stage.output_name,
        "batch": batch,
        "in_shape": list(in_specs[0].shape),
        "in_dtype": in_specs[0].dtype.name,
        "out_shape": list(stage.out_spec.shape),
        "out_dtype": stage.out_spec.dtype.name,
        "num_weights": len(leaves),
    }
    if len(in_specs) > 1:
        manifest["num_inputs"] = len(in_specs)
        manifest["in_shapes"] = [list(s.shape) for s in in_specs]
        manifest["in_dtypes"] = [s.dtype.name for s in in_specs]
    out = io.BytesIO()
    with zipfile.ZipFile(out, "w", zipfile.ZIP_DEFLATED) as z:
        z.writestr(_MANIFEST, json.dumps(manifest, indent=1))
        z.writestr(_PROGRAM, blob)
        z.writestr(_WEIGHTS, weights_blob(leaves))
    return out.getvalue()


def export_stage(stage: StageSpec, params: dict[str, Any], path: str,
                 *, batch: int = 1) -> None:
    """Serialize one pipeline stage to ``path`` (see export_stage_bytes)."""
    data = export_stage_bytes(stage, params, batch=batch)
    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    with open(path, "wb") as f:
        f.write(data)


class StageProgram:
    """A loaded stage artifact: callable, with swappable weights.

    ``fn(x)`` runs the stage's StableHLO program with its shipped weights
    on the local backend — no model code required (the analogue of the
    node's ``model_from_json`` + ``set_weights``, reference
    src/node.py:31-34).  ``reweight(blob)`` installs a fresh weight set
    (same shapes) without reloading the program — redeploy without
    restart.
    """

    def __init__(self, exported, leaves: list, manifest: dict):
        self.manifest = manifest
        self.device = None
        # the weights are ARGUMENTS of the jitted call: closed over,
        # they would be baked into the HLO as dense constants (tens of
        # MB per ResNet50 stage) and every reweight would recompile
        self._call = jax.jit(exported.call)
        self._install(leaves)

    def _install(self, leaves: list):
        if len(leaves) != self.manifest["num_weights"]:
            raise ValueError(
                f"expected {self.manifest['num_weights']} weight arrays, "
                f"got {len(leaves)}")
        # placed ONCE on the stage's device (the default device until
        # place() pins one); committed weights also pin the executable
        self._leaves = jax.device_put(list(leaves), self.device)

    def fn(self, *xs):
        """Run the stage: one array per merged branch path for a
        join-stage artifact (manifest["num_inputs"] > 1), else one."""
        if self.device is not None:
            # device_put of an array already resident there is a no-op,
            # so the device-resident (ici) hand-off path pays nothing
            xs = [jax.device_put(x, self.device) for x in xs]
        return self._call(self._leaves, *xs)

    def place(self, device) -> None:
        """Pin the program to one jax device: its weights move there and
        every call runs (and its output lives) there — the deployment
        half of the device-resident ``ici`` transport tier, where the
        UPSTREAM hop device_puts each activation onto this device and
        the program consumes it without any host round-trip."""
        self.device = device
        self._install(self._leaves)

    @property
    def weight_device_ids(self) -> list[int]:
        """Ids of the devices that hold the weights, read off the
        arrays' shardings (what is, not what was requested)."""
        return sorted({d.id for l in self._leaves for d in l.devices()})

    def reweight(self, blob: bytes):
        """Install a weights npz blob (shapes must match the artifact's);
        same shapes and dtypes, so the compiled program is reused."""
        new = _load_weights_blob(blob, self.manifest["num_weights"])
        for i, (old, nw) in enumerate(zip(self._leaves, new)):
            if old.shape != nw.shape or old.dtype != nw.dtype:
                raise ValueError(
                    f"weight {i}: artifact has {old.shape}/{old.dtype}, "
                    f"re-push has {nw.shape}/{nw.dtype}")
        self._install(new)

    def __call__(self, *xs):
        return self.fn(*xs)


def load_stage_program(src) -> StageProgram:
    """Load an exported stage from a path or bytes into a StageProgram."""
    f = io.BytesIO(src) if isinstance(src, (bytes, bytearray)) else src
    with zipfile.ZipFile(f) as z:
        manifest = json.loads(z.read(_MANIFEST).decode())
        if manifest.get("format") != "defer_tpu.stage.v1":
            raise ValueError(f"{src!r:.80}: not a defer_tpu stage artifact")
        exported = jax_export.deserialize(z.read(_PROGRAM))
        leaves = _load_weights_blob(z.read(_WEIGHTS),
                                    manifest["num_weights"])
    return StageProgram(exported, leaves, manifest)


def load_stage(path: str):
    """Back-compat loader: returns ``(fn, manifest)``."""
    prog = load_stage_program(path)
    return prog.fn, prog.manifest


def export_pipeline(stages, params, directory: str, *, batch: int = 1):
    """Export every stage of a partition to ``directory/stage_<i>.zip``."""
    paths = []
    for s in stages:
        p = os.path.join(directory, f"stage_{s.index}.zip")
        export_stage(s, params, p, batch=batch)
        paths.append(p)
    return paths
