"""defer_tpu — TPU-native distributed pipelined DNN inference.

A ground-up JAX/XLA re-design of the capabilities of ANRGUSC/DEFER
(arXiv:2201.06769): partition a model DAG into N sequential stages, place
stage i on device i of a TPU mesh, and stream inference inputs through the
chain with every stage concurrently busy.  The reference's TCP relay chain
becomes a single SPMD program (``shard_map`` + ``lax.ppermute`` over ICI);
its ZFP/LZ4 wire codec becomes bfloat16 HBM-resident buffers.

Quick start::

    import defer_tpu as dt

    graph = dt.models.resnet50()
    params = graph.init(jax.random.key(0))
    defer = dt.Defer(config=dt.DeferConfig(microbatch=1, chunk=16))
    outputs = defer.run(graph, params, inputs, num_stages=8)
"""

import sys as _sys
import time as _time

# ``setup.import`` (obs/profile.py): a span cannot wrap the import of
# its own module, so the two reads are taken here and recorded below
_t_import = _time.perf_counter()
_jax_preloaded = int("jax" in _sys.modules)

from . import models
from . import plan
from .graph.analysis import (auto_cut_points, max_activation_bytes,
                             total_flops, valid_cut_points)
from .graph.ir import GraphBuilder, LayerGraph, Op, ShapeSpec
from .graph.optimize import fold_batchnorm
from .graph.viz import summary, to_dot
from .ops import flash_attention
from .codec import (BlockFloatCodec, Codec, LosslessCodec, PipelineCodec,
                    RawCodec)
from .parallel.mesh import DATA_AXIS, STAGE_AXIS, pipeline_mesh
from .parallel.ring_attention import (SEQ_AXIS, ring_attention,
                                      sequence_parallel_attention)
from .parallel.ulysses import (sequence_parallel_attention_ulysses,
                               ulysses_attention)
from .parallel.distributed import (initialize, multihost_pipeline_mesh,
                                   process_local_batch)
from .parallel.expert import (EXPERT_AXIS, expert_parallel_fn,
                              expert_parallel_mesh, shard_moe_params)
from .parallel.tensor import (MODEL_AXIS, shard_tp_params,
                              tensor_parallel_fn, tensor_parallel_mesh)
from .partition.partitioner import partition
from .partition.stage import StageSpec
from .runtime.decode import PipelinedDecoder
from .runtime.dispatcher import Defer, DeferHandle, END_OF_STREAM
from .runtime.speculative import speculative_generate
from .runtime.mpmd import MpmdPipeline
from .runtime.spmd import SpmdPipeline
from .runtime.training import PipelineTrainer
from .utils.checkpoint import load_params, save_params
from .utils.export import export_pipeline, export_stage, load_stage
from .utils.config import DeferConfig
from .obs import (LatencyHistogram, MetricsRegistry, REGISTRY,
                  enable_tracing, export_chrome_trace, get_registry, tracer)
from .utils.metrics import PipelineMetrics, StopwatchWindow
from .utils.profiling import profile_pipeline
from .utils import compile_cache as _compile_cache
from .obs import profile as _obs_profile, trace as _obs_trace

__version__ = "0.1.0"

# nothing above compiles at import; every process of the package (its
# children too) resolves the same persistent-cache directory here
_compile_cache.configure()
# the compile listener from here on, unarmed: the programs of a weight
# draw or a checkpoint's load, which run before a decoder is built, have
# names too.  It fires only when jax traces or builds a program
_obs_profile.recompile_watcher().install()
_obs_trace.record_span("setup", "import", _t_import, _time.perf_counter(),
                       {"jax_preloaded": _jax_preloaded})

__all__ = [
    "GraphBuilder", "LayerGraph", "Op", "ShapeSpec", "StageSpec",
    "partition", "valid_cut_points", "auto_cut_points", "total_flops",
    "max_activation_bytes", "plan",
    "fold_batchnorm",
    "summary", "to_dot",
    "pipeline_mesh", "STAGE_AXIS", "DATA_AXIS",
    "SpmdPipeline", "MpmdPipeline", "PipelineTrainer", "PipelinedDecoder",
    "speculative_generate",
    "Defer", "DeferHandle", "DeferConfig",
    "END_OF_STREAM", "PipelineMetrics", "StopwatchWindow", "models",
    "SEQ_AXIS", "ring_attention", "sequence_parallel_attention",
    "sequence_parallel_attention_ulysses", "ulysses_attention",
    "flash_attention",
    "MODEL_AXIS", "shard_tp_params", "tensor_parallel_fn",
    "tensor_parallel_mesh",
    "EXPERT_AXIS", "expert_parallel_fn", "expert_parallel_mesh",
    "shard_moe_params",
    "initialize", "multihost_pipeline_mesh", "process_local_batch",
    "Codec", "BlockFloatCodec", "LosslessCodec", "PipelineCodec", "RawCodec",
    "save_params", "load_params", "profile_pipeline",
    "export_stage", "export_pipeline", "load_stage",
    "LatencyHistogram", "MetricsRegistry", "REGISTRY", "get_registry",
    "tracer", "enable_tracing", "export_chrome_trace",
]
