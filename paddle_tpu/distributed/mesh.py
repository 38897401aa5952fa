"""Device mesh + 4D(+sp) topology.

Reference parity: CommunicateTopology / HybridCommunicateGroup
(/root/reference/python/paddle/distributed/fleet/base/topology.py:54,140) with
axes data/pipe/sharding/model (:146-149).

TPU-native design: the topology IS a jax.sharding.Mesh with named axes
("dp", "pp", "sharding", "mp", "sp"). Communication groups are not NCCL
communicators but mesh axes — XLA routes collectives over ICI by axis name
(SURVEY.md §5 "Distributed communication backend"). A process-global mesh is
installed by fleet.init / init_mesh and consumed by sharded layers, the
compiled train step, and the eager collective API.
"""
from __future__ import annotations

import contextlib
import threading

import numpy as np

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

AXES = ("dp", "pp", "sharding", "mp", "sp")

_GLOBAL_MESH = None
_GLOBAL_TOPOLOGY = None
_TLS = threading.local()


@contextlib.contextmanager
def suppress_mesh():
    """Make `get_mesh()` return None in THIS THREAD for the duration —
    without touching the process-global mesh other threads may be tracing
    against. The serving engine wraps its traced forward in this: its
    sharding is fully explicit (in_shardings + PagedState.constrain), so
    the TP layers' training-mesh constraints must not leak in, while a
    concurrent training trace on another thread keeps its mesh."""
    _TLS.suppress = getattr(_TLS, "suppress", 0) + 1
    try:
        yield
    finally:
        _TLS.suppress -= 1


def build_mesh(degrees: dict, devices=None) -> Mesh:
    """degrees: e.g. {"dp": 2, "mp": 4}; axes default to 1 and are always
    present so PartitionSpecs can reference any axis."""
    devices = list(devices if devices is not None else jax.devices())
    shape = [int(degrees.get(a, 1)) for a in AXES]
    total = int(np.prod(shape))
    if total != len(devices):
        # allow using a prefix of devices (e.g. 4 of 8) for tests
        if total < len(devices):
            devices = devices[:total]
        else:
            raise ValueError(
                f"mesh degrees {degrees} need {total} devices, have {len(devices)}"
            )
    arr = np.asarray(devices).reshape(shape)
    return Mesh(arr, AXES)


def set_mesh(mesh: Mesh):
    global _GLOBAL_MESH
    _GLOBAL_MESH = mesh
    return mesh


def get_mesh() -> Mesh | None:
    if getattr(_TLS, "suppress", 0):
        return None
    return _GLOBAL_MESH


def init_mesh(degrees: dict, devices=None) -> Mesh:
    return set_mesh(build_mesh(degrees, devices))


def named_sharding(*spec) -> NamedSharding:
    mesh = get_mesh()
    if mesh is None:
        raise RuntimeError("no global mesh: call fleet.init or init_mesh first")
    return NamedSharding(mesh, PartitionSpec(*spec))


class CommunicateTopology:
    """Reference topology.py:54 — coordinate <-> rank bookkeeping."""

    def __init__(self, hybrid_group_names=("data", "pipe", "sharding", "model"), dims=(1, 1, 1, 1)):
        self._parallel_names = list(hybrid_group_names)
        self._dims = list(dims)
        self.coordinate = None
        self._world = int(np.prod(self._dims))
        shape = tuple(self._dims)
        self._coords = np.arange(self._world).reshape(shape)

    def get_hybrid_group_names(self):
        return self._parallel_names

    def get_dim(self, axis_name):
        return self._dims[self._parallel_names.index(axis_name)]

    def world_size(self):
        return self._world

    def get_rank(self, **kwargs):
        idx = tuple(kwargs[n] for n in self._parallel_names)
        return int(self._coords[idx])

    def get_coord(self, rank):
        return tuple(int(i) for i in np.unravel_index(rank, self._coords.shape))

    def get_axis_list(self, axis_name, index):
        ax = self._parallel_names.index(axis_name)
        sl = [slice(None)] * len(self._dims)
        sl[ax] = index
        return self._coords[tuple(sl)].reshape(-1).tolist()

    def get_dim_size(self, axis_name):
        return self.get_dim(axis_name)

    def get_comm_list(self, axis_name):
        ax = self._parallel_names.index(axis_name)
        moved = np.moveaxis(self._coords, ax, -1)
        return moved.reshape(-1, self._dims[ax]).tolist()


class HybridCommunicateGroup:
    """Reference topology.py:140. Wraps the mesh + this process's coordinates.

    Single-process SPMD note: under jit/GSPMD every device participates in the
    same program, so 'this process rank' means process_index-based placement
    (multi-host) or 0 (single host)."""

    def __init__(self, topology: CommunicateTopology = None, strategy=None):
        if topology is None:
            topology = CommunicateTopology()
        self._topo = topology
        self.global_rank = jax.process_index()
        names = topology.get_hybrid_group_names()

        def dim(name):
            return topology.get_dim(name) if name in names else 1

        self._dp_degree = dim("data")
        self._pp_degree = dim("pipe")
        self._sharding_degree = dim("sharding")
        self._mp_degree = dim("model")
        self._sp_degree = dim("sep") or 1
        degrees = {
            "dp": self._dp_degree,
            "pp": self._pp_degree,
            "sharding": self._sharding_degree,
            "mp": self._mp_degree,
            "sp": self._sp_degree,
        }
        self.mesh = init_mesh(degrees)
        coord = self._topo.get_coord(self.global_rank % self._topo.world_size())
        cmap = dict(zip(names, coord))
        self._dp_rank = cmap.get("data", 0)
        self._pp_rank = cmap.get("pipe", 0)
        self._sharding_rank = cmap.get("sharding", 0)
        self._mp_rank = cmap.get("model", 0)

    # --- reference API surface (topology.py:221 get_parallel_mode etc.) ----
    def get_parallel_mode(self):
        if self._pp_degree > 1:
            return "pipeline"
        if self._sharding_degree > 1:
            return "sharding_parallel"
        if self._mp_degree > 1:
            return "model_parallel"
        return "data_parallel"

    def topology(self):
        return self._topo

    def get_global_rank(self):
        return self.global_rank

    # data parallel
    def get_data_parallel_rank(self):
        return self._dp_rank

    def get_data_parallel_world_size(self):
        return self._dp_degree

    def get_data_parallel_group(self):
        return AxisGroup(self.mesh, "dp")

    # model parallel
    def get_model_parallel_rank(self):
        return self._mp_rank

    def get_model_parallel_world_size(self):
        return self._mp_degree

    def get_model_parallel_group(self):
        return AxisGroup(self.mesh, "mp")

    # pipeline
    def get_stage_id(self):
        return self._pp_rank

    def get_pipe_parallel_rank(self):
        return self._pp_rank

    def get_pipe_parallel_world_size(self):
        return self._pp_degree

    def get_pipe_parallel_group(self):
        return AxisGroup(self.mesh, "pp")

    def is_first_stage(self):
        return self._pp_rank == 0

    def is_last_stage(self):
        return self._pp_rank == self._pp_degree - 1

    # sharding
    def get_sharding_parallel_rank(self):
        return self._sharding_rank

    def get_sharding_parallel_world_size(self):
        return self._sharding_degree

    def get_sharding_parallel_group(self):
        return AxisGroup(self.mesh, "sharding")


class AxisGroup:
    """A 'process group' that is a named mesh axis (a ProcessGroupXla:
    collectives on it compile to XLA ICI ops)."""

    def __init__(self, mesh: Mesh, axis: str):
        self.mesh = mesh
        self.axis = axis

    @property
    def nranks(self):
        return self.mesh.shape[self.axis]

    world_size = nranks

    @property
    def rank(self):
        return 0

    def __repr__(self):
        return f"AxisGroup(axis={self.axis}, size={self.nranks})"


_HCG = None


def set_hybrid_communicate_group(hcg):
    global _HCG
    _HCG = hcg


def get_hybrid_communicate_group():
    return _HCG


fleet_hcg = get_hybrid_communicate_group
