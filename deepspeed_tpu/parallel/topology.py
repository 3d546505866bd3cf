"""Named-axis process topology and device-mesh construction.

TPU-native equivalent of the reference's ``runtime/pipe/topology.py``:
``ProcessTopology`` (reference :12) — named-axis cartesian rank mapping — and
``PipeDataParallelTopology``/``PipeModelDataParallelTopology`` (reference :232/:244).
On TPU the topology *is* a ``jax.sharding.Mesh``; this module keeps the reference's
rank-math API (``get_rank``, ``get_coord``, ``get_axis_comm_lists``, filtering) because
launchers, checkpoint naming, and pipeline schedules all consume it, and builds the
Mesh from it.

Axis order convention: slower-varying axes first (the reference puts ``pipe`` outermost
for the same reason); for multi-slice TPU deployments the outermost axis should be the
one riding DCN (usually ``data``/``pipe``), inner axes ride ICI.
"""

import itertools
from collections import namedtuple

import numpy as np

from ..config.base import ConfigError

# Canonical mesh axis names for the whole framework. Everything (ZeRO sharding specs,
# TP rules, MoE all_to_all, ring attention, pipeline ppermute) refers to these names.
PIPE_AXIS = "pipe"
DATA_AXIS = "data"
MODEL_AXIS = "model"
SEQ_AXIS = "seq"
EXPERT_AXIS = "expert"

# Mesh layout order (outermost first). pipe/data outermost so that multi-slice DCN
# traffic is the low-frequency pipeline/data-parallel traffic.
CANONICAL_AXIS_ORDER = (PIPE_AXIS, DATA_AXIS, EXPERT_AXIS, SEQ_AXIS, MODEL_AXIS)


class ProcessCoord(dict):
    """Mapping axis-name -> coordinate, attribute-accessible like the reference's
    namedtuple coords (``topology.py:12``)."""

    def __getattr__(self, name):
        try:
            return self[name]
        except KeyError:
            raise AttributeError(name)


class ProcessTopology:
    """Cartesian product topology over named axes (reference ``topology.py:12``)."""

    def __init__(self, axes, dims):
        if len(axes) != len(dims):
            raise ConfigError(f"axes {axes} and dims {dims} length mismatch")
        self.axes = list(axes)
        self.dims = list(int(d) for d in dims)
        for name, d in zip(self.axes, self.dims):
            if d < 1:
                raise ConfigError(f"axis {name} has invalid size {d}")
        self._coord_cls = namedtuple("ProcessCoordT", self.axes)
        self.mapping = {}
        for rank, coord in enumerate(itertools.product(*[range(d) for d in self.dims])):
            self.mapping[self._coord_cls(*coord)] = rank

    def world_size(self):
        return int(np.prod(self.dims)) if self.dims else 1

    def get_dim(self, axis):
        return self.dims[self.axes.index(axis)] if axis in self.axes else 0

    def get_rank(self, **coord_kwargs):
        """Rank of the process at the given full coordinate (reference :49)."""
        if sorted(coord_kwargs) != sorted(self.axes):
            raise ConfigError(f"get_rank requires all axes {self.axes}, got {sorted(coord_kwargs)}")
        return self.mapping[self._coord_cls(**coord_kwargs)]

    def get_coord(self, rank):
        for coord, r in self.mapping.items():
            if r == rank:
                return coord
        raise ConfigError(f"rank {rank} not in topology")

    def get_rank_repr(self, rank, omit_axes=(PIPE_AXIS, DATA_AXIS), inner_sep="_", outer_sep="-"):
        """String like 'model_00' used in checkpoint filenames (reference :81)."""
        omit_axes = list(omit_axes)
        axes = [a for a in self.axes if a not in omit_axes]
        names = []
        coord = self.get_coord(rank)
        for ax in axes:
            names.append(f"{ax}{inner_sep}{getattr(coord, ax):02d}")
        return outer_sep.join(names)

    def get_axis_list(self, axis, idx):
        """All ranks whose coordinate along ``axis`` equals ``idx`` (reference :106)."""
        ax_idx = self.axes.index(axis)
        return sorted(rank for coord, rank in self.mapping.items() if coord[ax_idx] == idx)

    def get_axis_comm_lists(self, axis):
        """Communicator rank lists along ``axis``: for every combination of the other
        axes, the list of ranks that vary only in ``axis`` (reference :127). This is
        exactly what a process group / mesh-axis collective spans."""
        if axis not in self.axes:
            return []
        other_axes = [a for a in self.axes if a != axis]
        lists = []
        for combo in itertools.product(*[range(self.get_dim(a)) for a in other_axes]):
            other = dict(zip(other_axes, combo))
            ranks = [self.get_rank(**{axis: i, **other}) for i in range(self.get_dim(axis))]
            lists.append(ranks)
        return lists

    def filter_match(self, **filter_kwargs):
        """Ranks matching the partial coordinate (reference :153)."""
        def matches(coord):
            return all(getattr(coord, k) == v for k, v in filter_kwargs.items())

        return sorted(rank for coord, rank in self.mapping.items() if matches(coord))

    def __str__(self):
        return f"ProcessTopology(axes={self.axes}, dims={self.dims})"


def resolve_mesh_dims(mesh_config, n_devices):
    """Resolve a MeshConfig (-1 = infer on data axis) against the device count.

    Returns an ordered dict axis-name -> size following CANONICAL_AXIS_ORDER.
    """
    sizes = {
        PIPE_AXIS: mesh_config.pipe,
        DATA_AXIS: mesh_config.data,
        EXPERT_AXIS: mesh_config.expert,
        SEQ_AXIS: mesh_config.seq,
        MODEL_AXIS: mesh_config.model,
    }
    for name, v in sizes.items():
        if v == 0 or v < -1:
            raise ConfigError(f"Mesh axis '{name}' has invalid size {v} (use -1 to infer)")
    n_infer = sum(1 for v in sizes.values() if v == -1)
    if n_infer > 1:
        raise ConfigError("Only one mesh axis may be -1 (inferred)")
    fixed = int(np.prod([v for v in sizes.values() if v != -1]))
    if n_infer == 1:
        if n_devices % fixed:
            raise ConfigError(
                f"Cannot infer mesh axis: {n_devices} devices not divisible by {fixed}"
            )
        for k, v in sizes.items():
            if v == -1:
                sizes[k] = n_devices // fixed
    else:
        if fixed != n_devices:
            raise ConfigError(
                f"Mesh {sizes} has {fixed} slots but there are {n_devices} devices"
            )
    return {ax: sizes[ax] for ax in CANONICAL_AXIS_ORDER}


def build_mesh(mesh_config=None, devices=None):
    """Build the framework-wide ``jax.sharding.Mesh``.

    The reference builds process groups per axis from ``ProcessTopology``
    (``topology.py:251`` ``PipelineParallelGrid``); here one Mesh with named axes
    replaces all of them — XLA collectives take the axis name.
    """
    import jax
    from jax.sharding import Mesh

    if devices is None:
        devices = jax.devices()
    if mesh_config is None:
        from ..config.config import MeshConfig

        mesh_config = MeshConfig()
    dims = resolve_mesh_dims(mesh_config, len(devices))
    axis_names = tuple(dims.keys())
    shape = tuple(dims.values())
    # mesh_utils gives ICI-aware device orderings on TPU slices and its
    # errors there are real (a shape the slice cannot host); CPU / virtual
    # devices have no topology to respect and take a plain reshape.
    if devices[0].platform == "tpu":
        from jax.experimental import mesh_utils

        device_array = mesh_utils.create_device_mesh(shape, devices=devices)
    else:
        device_array = np.asarray(devices).reshape(shape)
    return Mesh(device_array, axis_names)


class PipelineParallelGrid:
    """Rank bookkeeping for pipeline runs (reference ``topology.py:251``).

    Carries the topology plus convenience accessors (stage id, dp id, adjacent
    stages). Collectives themselves go through mesh axis names, not rank lists.
    """

    def __init__(self, topology):
        self._topo = topology
        self.pipe_parallel_size = topology.get_dim(PIPE_AXIS) or 1
        self.data_parallel_size = topology.get_dim(DATA_AXIS) or 1
        self.model_parallel_size = topology.get_dim(MODEL_AXIS) or 1

    @property
    def topology(self):
        return self._topo

    def stage_of_rank(self, rank):
        if PIPE_AXIS not in self._topo.axes:
            return 0
        return getattr(self._topo.get_coord(rank), PIPE_AXIS)

    def dp_group_of_rank(self, rank):
        if DATA_AXIS not in self._topo.axes:
            return [rank]
        coord = self._topo.get_coord(rank)
        other = {a: getattr(coord, a) for a in self._topo.axes if a != DATA_AXIS}
        return self._topo.filter_match(**other)

    def stage_to_global(self, stage_id, **kwargs):
        return self._topo.filter_match(**{PIPE_AXIS: stage_id, **kwargs})

    def is_first_stage(self, rank):
        return self.stage_of_rank(rank) == 0

    def is_last_stage(self, rank):
        return self.stage_of_rank(rank) == self.pipe_parallel_size - 1


def topology_from_mesh_dims(dims):
    """ProcessTopology over the canonical axes with the given sizes dict."""
    axes = list(CANONICAL_AXIS_ORDER)
    return ProcessTopology(axes=axes, dims=[dims.get(a, 1) for a in axes])
