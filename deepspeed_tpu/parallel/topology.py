"""Process/device topology math.

TPU-native analog of the reference's ``deepspeed/runtime/pipe/topology.py``
(``ProcessTopology`` :9, ``PipeModelDataParallelTopology`` :243).  The math is
device-free and identical in spirit: a cartesian grid of named axes maps a linear
rank to a coordinate.  On TPU the *same* abstraction materialises as a
``jax.sharding.Mesh`` (axes become mesh axis names and collectives ride ICI), so
``MeshTopology`` below carries both views: pure coordinate math for schedulers and
tests, and the live ``Mesh`` for pjit/shard_map.

Canonical axis order (outermost → innermost): ``pp, dp, ep, sp, tp``.
 - ``pp``  pipeline stages (slowest-changing; cross-stage traffic is point-to-point)
 - ``dp``  expert-aware data parallel (ZeRO shards over (dp, ep) combined)
 - ``ep``  expert parallel: experts shard over this axis; the full data-parallel
           world is (dp × ep), mirroring reference ``utils/groups.py`` where expert
           groups subdivide the DP world
 - ``sp``  sequence/context parallel (Ulysses all-to-all / ring attention)
 - ``tp``  tensor parallel (innermost: highest-bandwidth ICI neighbours)
"""

from __future__ import annotations

from collections import namedtuple
from itertools import product
from typing import Dict, List, Optional, Sequence, Tuple


class ProcessTopology:
    """Maps n-dim grid coordinates to linear ranks, row-major (first axis slowest).

    Pure-python; mirrors the reference API surface so pipeline/grid code and tests
    carry over conceptually (reference ``pipe/topology.py:9``).
    """

    def __init__(self, axes: Sequence[str], dims: Sequence[int]):
        self.axes = list(axes)  # names of each topology axis
        self.dims = list(dims)  # length of each axis
        assert len(self.axes) == len(self.dims)
        self.ProcessCoord = namedtuple("ProcessCoord", self.axes)
        self.mapping: Dict[Tuple[int, ...], int] = {}
        ranges = [range(d) for d in self.dims]
        for global_rank, coord in enumerate(product(*ranges)):
            key = {axis: coord[self.axes.index(axis)] for axis in self.axes}
            key = self.ProcessCoord(**key)
            self.mapping[key] = global_rank

    def get_rank(self, **coord_kwargs) -> int:
        if len(coord_kwargs) != len(self.axes):
            raise ValueError(f"get_rank() needs all axes {self.axes}, got {coord_kwargs}")
        key = self.ProcessCoord(**coord_kwargs)
        assert key in self.mapping, f"coord {key} not in topology"
        return self.mapping[key]

    def get_axis_names(self) -> List[str]:
        return self.axes

    def get_rank_repr(self, rank: int, omit_axes: Sequence[str] = ("data",),
                      inner_sep: str = "_", outer_sep: str = "-") -> str:
        omit_axes = list(omit_axes)
        axes = [a for a in self.axes if a not in omit_axes]
        names = []
        for ax in axes:
            ax_rank = getattr(self.get_coord(rank=rank), ax)
            names.append(f"{ax}{inner_sep}{ax_rank:02d}")
        return outer_sep.join(names)

    def get_dim(self, axis: str) -> int:
        if axis not in self.axes:
            return 0
        return self.dims[self.axes.index(axis)]

    def get_coord(self, rank: int):
        for coord, idx in self.mapping.items():
            if idx == rank:
                return coord
        raise ValueError(f"rank {rank} not found in topology")

    def get_axis_comm_lists(self, axis: str) -> List[List[int]]:
        """Lists of ranks that vary along ``axis`` with all other coords fixed.

        These are exactly the process groups the reference builds for each axis.
        """
        if axis not in self.axes:
            return []
        other_axes = [a for a in self.axes if a != axis]
        lists = []
        ranges = [range(self.get_dim(a)) for a in other_axes]
        for coord in product(*ranges):
            other_keys = {a: coord[other_axes.index(a)] for a in other_axes}
            sub = [self.get_rank(**other_keys, **{axis: i}) for i in range(self.get_dim(axis))]
            lists.append(sub)
        return lists

    def filter_match(self, **filter_kwargs) -> List[int]:
        """All ranks whose coordinates match the given axis=value filters."""

        def _filter_helper(x):
            for key, val in filter_kwargs.items():
                if getattr(x, key) != val:
                    return False
            return True

        coords = filter(_filter_helper, self.mapping.keys())
        return [self.mapping[coord] for coord in coords]

    def get_axis_list(self, axis: str, idx: int) -> List[int]:
        return self.filter_match(**{axis: idx})

    @property
    def world_size(self) -> int:
        size = 1
        for d in self.dims:
            size *= d
        return size

    def __str__(self):
        return str(self.mapping)


class PipeModelDataParallelTopology(ProcessTopology):
    """3D topology used by hybrid pipeline/model/data parallelism.

    Same axis naming as the reference (``pipe/topology.py:243``).
    """

    def __init__(self, num_pp: int, num_mp: int, num_dp: int):
        super().__init__(axes=["pipe", "data", "model"], dims=[num_pp, num_dp, num_mp])


class PipeDataParallelTopology(ProcessTopology):

    def __init__(self, num_pp: int, num_dp: int):
        super().__init__(axes=["pipe", "data"], dims=[num_pp, num_dp])


# Canonical mesh axis names used by the whole framework.
PP_AXIS = "pp"
DP_AXIS = "dp"
EP_AXIS = "ep"
SP_AXIS = "sp"
TP_AXIS = "tp"
MESH_AXES = (PP_AXIS, DP_AXIS, EP_AXIS, SP_AXIS, TP_AXIS)
#: Axes a batch dimension is sharded over — the "full DP world" of the reference.
DATA_AXES = (DP_AXIS, EP_AXIS)
#: Axes ZeRO shards dense optimizer/gradient/parameter state over.
ZERO_AXES = (DP_AXIS, EP_AXIS)


class MeshTopology:
    """Named-axis device grid + live ``jax.sharding.Mesh``.

    ``dp=-1`` (default) absorbs all devices not claimed by other axes.  The same
    object answers pure coordinate queries (via an internal :class:`ProcessTopology`)
    and provides the ``Mesh`` that every pjit/shard_map in the framework runs under.
    """

    def __init__(self, pp: int = 1, dp: int = -1, ep: int = 1, sp: int = 1, tp: int = 1,
                 devices=None, allow_split_physical_axes: bool = False):
        import jax

        if devices is None:
            devices = jax.devices()
        n = len(devices)
        sizes = {"pp": pp, "dp": dp, "ep": ep, "sp": sp, "tp": tp}
        fixed = 1
        for name, s in sizes.items():
            if s != -1:
                assert s >= 1, f"axis {name} must be >=1 or -1, got {s}"
                fixed *= s
        if dp == -1:
            assert n % fixed == 0, (
                f"cannot infer dp: {n} devices not divisible by pp*ep*sp*tp={fixed}")
            sizes["dp"] = n // fixed
        total = 1
        for s in sizes.values():
            total *= s
        assert total == n, (
            f"mesh {sizes} needs {total} devices but {n} are available")

        self.axis_sizes: Dict[str, int] = {a: sizes[a] for a in MESH_AXES}
        self._proc_topo = ProcessTopology(list(MESH_AXES),
                                          [self.axis_sizes[a] for a in MESH_AXES])
        self._devices = devices
        self._allow_split = allow_split_physical_axes
        self._mesh = None

    @property
    def mesh(self):
        """Lazily build the jax Mesh (device placement via mesh_utils for ICI locality)."""
        if self._mesh is None:
            import jax
            import numpy as np
            from jax.sharding import Mesh

            shape = tuple(self.axis_sizes[a] for a in MESH_AXES)
            if self._devices[0].platform == "cpu" or \
                    len(self._devices) < jax.device_count():
                # virtual host devices have no interconnect to place for;
                # a sub-host group (one serving replica's chips) is not a
                # full physical torus, so it keeps enumeration order
                dev_array = np.asarray(self._devices).reshape(shape)
            else:
                from jax.experimental import mesh_utils

                # raises when the shape does not map onto the physical
                # topology — a silently mis-placed mesh would put tp
                # neighbours across the slow links
                dev_array = mesh_utils.create_device_mesh(
                    shape, devices=self._devices,
                    allow_split_physical_axes=self._allow_split)
            self._mesh = Mesh(dev_array, MESH_AXES)
        return self._mesh

    # ---- size queries (names mirror reference utils/groups.py) ----
    def get_dim(self, axis: str) -> int:
        return self.axis_sizes.get(axis, 0)

    @property
    def data_parallel_size(self) -> int:
        return self.axis_sizes[DP_AXIS] * self.axis_sizes[EP_AXIS]

    @property
    def expert_parallel_size(self) -> int:
        return self.axis_sizes[EP_AXIS]

    @property
    def expert_data_parallel_size(self) -> int:
        return self.axis_sizes[DP_AXIS]

    @property
    def model_parallel_size(self) -> int:
        return self.axis_sizes[TP_AXIS]

    @property
    def tensor_parallel_size(self) -> int:
        return self.axis_sizes[TP_AXIS]

    @property
    def pipe_parallel_size(self) -> int:
        return self.axis_sizes[PP_AXIS]

    @property
    def sequence_parallel_size(self) -> int:
        return self.axis_sizes[SP_AXIS]

    @property
    def world_size(self) -> int:
        return self._proc_topo.world_size

    @property
    def topology(self) -> ProcessTopology:
        return self._proc_topo

    def coord_of(self, device_rank: int):
        return self._proc_topo.get_coord(device_rank)

    def __repr__(self):
        dims = ", ".join(f"{a}={s}" for a, s in self.axis_sizes.items())
        return f"MeshTopology({dims})"


def normalize_mesh_config(mesh_cfg: Optional[dict]) -> dict:
    """Canonicalize the ``"mesh"`` config block's axis aliases (single source
    of truth — also used by ``deepspeed_tpu.initialize`` for engine selection)."""
    aliases = {"pipeline_parallel_size": "pp", "data_parallel_size": "dp",
               "expert_parallel_size": "ep", "sequence_parallel_size": "sp",
               "tensor_parallel_size": "tp", "model_parallel_size": "tp"}
    norm = {}
    for k, v in dict(mesh_cfg or {}).items():
        norm[aliases.get(k, k)] = v
    allowed = set(MESH_AXES) | {"allow_split_physical_axes"}
    unknown = set(norm) - allowed
    if unknown:
        raise ValueError(f"unknown mesh axes {sorted(unknown)}; allowed: {sorted(allowed)}")
    return norm


def topology_from_config(mesh_cfg: Optional[dict], devices=None) -> MeshTopology:
    """Build a MeshTopology from the ``"mesh"`` block of the JSON config."""
    return MeshTopology(devices=devices, **normalize_mesh_config(mesh_cfg))
