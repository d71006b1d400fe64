"""Device mesh and topology management.

TPU-native replacement for the reference's process-group machinery
(``deepspeed/utils/groups.py`` — data/model/expert/sequence parallel group
creation and caching, plus ``runtime/pipe/topology.py`` ProcessTopology /
PipeModelDataParallelGrid). Instead of creating and caching
``torch.distributed`` groups per parallelism flavor, we build ONE
``jax.sharding.Mesh`` with named axes

    (data, seq, pipe, expert, model)

and every "group" from the reference becomes an axis name (or tuple of axis
names) that collectives/shardings refer to. Hierarchy: the axis order places
``model`` innermost so tensor-parallel collectives ride the fastest ICI
links, matching how the reference nests model-parallel groups inside nodes
(groups.py:64 _create_model_parallel).

The reference's derived groups map as:
  data_parallel group          -> axis 'data'
  model_parallel group         -> axis 'model'
  pipe stages                  -> axis 'pipe'
  expert_parallel group        -> axis 'expert' (reference: _create_expert_and_data_parallel, groups.py:113)
  expert_data_parallel group   -> axes ('data',) with expert folded — see expert_data_axes()
  sequence_parallel group      -> axis 'seq' (groups.py:468 _get_sequence_parallel_group)
  sequence_data_parallel group -> axes ('data','seq') (groups.py:489)
  ZeRO param-partition group   -> axes ('data','seq') — ZeRO shards over all
                                  replica dimensions (engine.py:1122 uses the
                                  seq_data_parallel group as ZeRO's dp group)
"""

from __future__ import annotations

import os
from typing import Dict, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from ..config import MeshConfig
from ..utils.logging import log_dist

# Canonical axis order, outermost → innermost. 'zshard' is the secondary
# ZeRO partition axis (size 1 unless ZeRO++ hpZ / MiCS factor the data
# dimension): data-parallel replicas are laid out as data × zshard with
# zshard the *inner* (intra-slice, fast-ICI) factor — the analog of the
# reference's intra-node secondary groups (utils/groups.py:356
# _create_zero_param_parallel_group, runtime/zero/mics.py:55 MiCS_Init).
MESH_AXES: Tuple[str, ...] = ("data", "zshard", "seq", "pipe", "expert", "model")


class Topology:
    """Owns the device mesh and answers every group/rank/size query.

    The reference answers these via cached torch process groups
    (groups.py get_*_parallel_group/rank/world_size); here they are simple
    mesh-shape lookups.
    """

    def __init__(self, mesh: Mesh):
        self.mesh = mesh
        self._sizes = dict(zip(mesh.axis_names, mesh.devices.shape))

    # -- construction ---------------------------------------------------
    @classmethod
    def build(cls, mesh_config: Optional[MeshConfig] = None,
              devices: Optional[Sequence[jax.Device]] = None,
              zero_inner: int = 1) -> "Topology":
        """``zero_inner`` > 1 factors the data-parallel dimension into
        data × zshard (zshard = inner, size ``zero_inner``) for ZeRO++ hpZ /
        MiCS hierarchical sharding."""
        mesh_config = mesh_config or MeshConfig()
        if devices is None:
            devices = jax.devices()
        sizes = mesh_config.resolve(len(devices))
        sizes.setdefault("zshard", 1)
        if zero_inner > 1:
            dp = sizes["data"] * sizes["zshard"]
            if dp % zero_inner != 0:
                raise ValueError(
                    f"zero_inner={zero_inner} must divide the data-parallel "
                    f"size {dp} (hpz_partition_size / mics_shard_size)")
            sizes["data"], sizes["zshard"] = dp // zero_inner, zero_inner
        shape = tuple(sizes[a] for a in MESH_AXES)
        dev_array = np.asarray(devices).reshape(shape)
        mesh = Mesh(dev_array, MESH_AXES)
        log_dist(f"Built device mesh {dict(zip(MESH_AXES, shape))} over {len(devices)} devices")
        return cls(mesh)

    @classmethod
    def build_virtual(cls, sizes: Dict[str, int]) -> "Topology":
        """Build a mesh with explicit axis sizes (tests / dry runs), using
        only as many devices as the axes require. A 'zshard' entry factors
        the data dimension (hpZ / MiCS inner partition)."""
        inner = sizes.get("zshard", 1)
        cfg = MeshConfig(**{a: sizes.get(a, 1) for a in MeshConfig.AXES})
        n = 1
        for a in MeshConfig.AXES:
            n *= sizes.get(a, 1)
        return cls.build(cfg, devices=jax.devices()[:n], zero_inner=inner)

    # -- size / rank queries (parity with groups.py get_* helpers) ------
    def axis_size(self, axis: str) -> int:
        return self._sizes[axis]

    @property
    def world_size(self) -> int:
        return int(np.prod(list(self._sizes.values())))

    @property
    def data_parallel_size(self) -> int:
        return self._sizes["data"] * self._sizes["zshard"]

    @property
    def zero_secondary_size(self) -> int:
        """Size of the inner (hpZ / MiCS) partition factor."""
        return self._sizes["zshard"]

    def data_axes(self) -> Tuple[str, ...]:
        """Mesh axes jointly forming the data-parallel dimension."""
        return ("data", "zshard") if self._sizes["zshard"] > 1 else ("data",)

    @property
    def model_parallel_size(self) -> int:
        return self._sizes["model"]

    @property
    def pipe_parallel_size(self) -> int:
        return self._sizes["pipe"]

    @property
    def expert_parallel_size(self) -> int:
        return self._sizes["expert"]

    @property
    def sequence_parallel_size(self) -> int:
        return self._sizes["seq"]

    @property
    def sequence_data_parallel_size(self) -> int:
        # reference groups.py:489 _get_sequence_data_parallel_group
        return self._sizes["seq"] * self.data_parallel_size

    def zero_partition_axes(self) -> Tuple[str, ...]:
        """Axes ZeRO shards params/grads/optimizer state over.

        The reference uses the (seq-)data-parallel group as ZeRO's dp group
        (engine.py:1122); expert replicas join for non-expert params.
        """
        axes = [a for a in ("data", "zshard", "seq") if self._sizes[a] > 1]
        return tuple(axes) if axes else ("data",)

    def zero_secondary_axes(self) -> Tuple[str, ...]:
        """Inner partition axes for hpZ secondary param shards / MiCS
        sub-group sharding (reference partition_parameters.py:883,
        mics.py:227): the fast-ICI factor of the data dimension (+ seq)."""
        axes = [a for a in ("zshard", "seq") if self._sizes[a] > 1]
        return tuple(axes) if axes else ("zshard",)

    def expert_data_axes(self) -> Tuple[str, ...]:
        """Replica axes for expert parameters (expert-data-parallel group,
        reference groups.py:113)."""
        axes = [a for a in ("data", "zshard", "seq") if self._sizes[a] > 1]
        return tuple(axes) if axes else ("data",)

    # -- sharding helpers ----------------------------------------------
    def sharding(self, *spec) -> NamedSharding:
        return NamedSharding(self.mesh, PartitionSpec(*spec))

    def replicated(self) -> NamedSharding:
        return NamedSharding(self.mesh, PartitionSpec())

    def data_sharding(self, ndim: int = 1) -> NamedSharding:
        """Batch sharding: leading dim over the data axes — and 'seq' folds
        into batch for the dataloader when sequence parallelism is off."""
        spec: list = [None] * ndim
        spec[0] = self.data_axes()
        return NamedSharding(self.mesh, PartitionSpec(*spec))

    def batch_sharding(self, ndim: int = 2) -> NamedSharding:
        """[batch, seq, ...] sharding: batch over the data axes, seq over
        'seq'."""
        spec: list = [None] * ndim
        spec[0] = self.data_axes()
        if ndim > 1 and self._sizes["seq"] > 1:
            spec[1] = "seq"
        return NamedSharding(self.mesh, PartitionSpec(*spec))

    def __repr__(self) -> str:
        return f"Topology({self._sizes})"


# ----------------------------------------------------------------------
# Module-level singleton, mirroring the reference's groups.py module state.
_TOPOLOGY: Optional[Topology] = None


def initialize_topology(mesh_config: Optional[MeshConfig] = None,
                        devices: Optional[Sequence[jax.Device]] = None,
                        force: bool = False) -> Topology:
    global _TOPOLOGY
    if _TOPOLOGY is None or force:
        _TOPOLOGY = Topology.build(mesh_config, devices)
    return _TOPOLOGY


def get_topology() -> Topology:
    if _TOPOLOGY is None:
        return initialize_topology()
    return _TOPOLOGY


def set_topology(topo: Topology) -> None:
    global _TOPOLOGY
    _TOPOLOGY = topo


def reset_topology() -> None:
    global _TOPOLOGY
    _TOPOLOGY = None
