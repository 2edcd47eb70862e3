"""Hybrid-parallel model wrappers (parity: fleet/meta_parallel/).

The reference wraps models in PipelineParallel/TensorParallel/ShardingParallel
classes that install communication hooks. TPU-native equivalent: annotate
parameter shardings (mp/fsdp axes) on the existing Layer tree and let GSPMD
place collectives; pipeline parallelism has its own explicit scheduler in
distributed/pipeline.py.
"""

from __future__ import annotations

import re

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from ...core.mesh import trimmed_spec
from ...nn.module import Layer

__all__ = ["apply_hybrid_shardings", "fsdp_rules", "TensorParallel",
           "ShardingParallel", "SegmentParallel"]

# Minimum parameter size worth sharding on the fsdp axis — the analogue of
# GroupShardedStage3's segment_size=2^20 threshold (SURVEY §B.2).
FSDP_MIN_SIZE = 2 ** 20


def fsdp_rules(params: dict[str, jax.Array], axis: str = "fsdp",
               min_size: int = FSDP_MIN_SIZE) -> dict[str, PartitionSpec]:
    """Shard the largest dim of each big param on the fsdp axis."""
    specs = {}
    for k, v in params.items():
        if v.size >= min_size and v.ndim >= 1:
            dim = int(np.argmax(v.shape))
            entries = [None] * v.ndim
            entries[dim] = axis
            specs[k] = PartitionSpec(*entries)
        else:
            specs[k] = PartitionSpec()
    return specs


def apply_hybrid_shardings(model: Layer, mesh: Mesh, strategy=None) -> Layer:
    """Place every param with its layer-declared spec (mp/TP), then overlay
    fsdp sharding for large unsharded params. Degrees of 1 make the axes
    vanish (PartitionSpec entries over size-1 axes are no-ops)."""
    params = model.param_dict()
    declared = model.spec_dict()
    fsdp = fsdp_rules({k: v for k, v in params.items()
                       if not declared.get(k)})
    new = {}
    for k, v in params.items():
        spec = declared.get(k)
        pspec = PartitionSpec(*spec) if spec else fsdp.get(k, PartitionSpec())
        # trimmed: else step 2 of a TrainStep, fed step 1's outputs,
        # sees new spellings of the same shardings and compiles again
        new[k] = jax.device_put(v, NamedSharding(mesh, trimmed_spec(*pspec)))
    model.set_state_dict(new)
    # buffers replicate
    bufs = model.buffer_dict()
    if bufs:
        rep = {k: jax.device_put(v, NamedSharding(mesh, PartitionSpec()))
               for k, v in bufs.items()}
        model.set_state_dict(rep)
    return model


class _Passthrough(Layer):
    def __init__(self, layers: Layer):
        super().__init__()
        self._layers = layers

    def forward(self, *args, **kw):
        return self._layers(*args, **kw)


class TensorParallel(_Passthrough):
    """Parity shim: TP is expressed by layer weight_specs (ColumnParallelLinear
    == Linear(weight_spec=(None,'mp')))."""


class ShardingParallel(_Passthrough):
    pass


class SegmentParallel(_Passthrough):
    pass
