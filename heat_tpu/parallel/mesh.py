"""Mesh-backed communication context.

TPU-native replacement for the reference's ``MPICommunication``
(heat/core/communication.py:120-1895).  Where the reference wraps ~40 MPI
primitives around torch tensors, here a :class:`MeshComm` wraps a
``jax.sharding.Mesh``:

* the reference's *rank/size* become device positions along the mesh's split
  axis (``heat/core/communication.py:120-160``),
* the reference's ``chunk()`` block-distribution rule
  (``heat/core/communication.py:161-218``) is re-derived for GSPMD's canonical
  even-chunk layout (``ceil(n/N)`` per shard, trailing shards truncated), so
  ``lshape_map`` metadata always matches what XLA actually places on each
  device,
* every explicit collective disappears into XLA — a ``DNDarray`` op under
  ``jit`` with the right ``PartitionSpec`` emits all-reduce / all-gather /
  all-to-all / collective-permute on ICI automatically.

Multi-host initialization (the reference's ``mpirun`` bootstrap,
communication.py:1909-1921) maps to :func:`init_distributed` — call it once
before building a mesh; :func:`hybrid_mesh` then lays DCN-spanning axes over
slices/hosts and ICI axes within a slice.
"""

from __future__ import annotations

import os
from typing import List, Optional, Tuple

import numpy as np

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from ..core import envparse

__all__ = [
    "Communication",
    "MeshComm",
    "get_comm",
    "use_comm",
    "sanitize_comm",
    "world",
    "local_mesh",
    "init_distributed",
    "hybrid_mesh",
]

#: canonical name of the mesh axis that backs the DNDarray ``split`` dimension
SPLIT_AXIS = "split"


class Communication:
    """Abstract base for communication contexts (reference: Communication ABC,
    heat/core/communication.py:88-118)."""

    @staticmethod
    def is_distributed() -> bool:
        raise NotImplementedError()

    def chunk(self, shape, split, rank=None):
        raise NotImplementedError()


def _ceil_div(a: int, b: int) -> int:
    return -(-a // b)


class MeshComm(Communication):
    """A communication context backed by a JAX device mesh.

    Parameters
    ----------
    mesh : jax.sharding.Mesh, optional
        The device mesh. If ``None``, a 1-D mesh over all visible devices is
        created with axis name ``"split"``.
    split_axis : str
        The mesh axis name that DNDarray ``split`` dimensions are sharded over.

    Notes
    -----
    ``nranks``/``rank`` mirror the reference's process semantics
    (communication.py:151-160) but count *devices along the split axis*, since
    on TPU the unit of SPMD parallelism is the chip, not the host process.
    """

    def __init__(self, mesh: Optional[Mesh] = None, split_axis: str = SPLIT_AXIS):
        if mesh is None:
            devices = np.array(jax.devices())
            mesh = Mesh(devices, (split_axis,))
        if split_axis not in mesh.axis_names:
            raise ValueError(
                f"split_axis {split_axis!r} not in mesh axes {mesh.axis_names}"
            )
        self.mesh = mesh
        self.split_axis = split_axis

    # ------------------------------------------------------------------ basic
    @property
    def size(self) -> int:
        """Number of devices along the split axis."""
        return int(self.mesh.shape[self.split_axis])

    @property
    def rank(self) -> int:
        """Index of this *process* (multi-host); 0 in single-controller runs."""
        return jax.process_index()

    @property
    def n_devices(self) -> int:
        return int(np.prod(list(self.mesh.shape.values())))

    @staticmethod
    def is_distributed() -> bool:
        return len(jax.devices()) > 1

    def __repr__(self) -> str:
        return f"MeshComm(mesh={self.mesh!r}, split_axis={self.split_axis!r})"

    # ------------------------------------------------------------- partitions
    def chunk(
        self, shape: Tuple[int, ...], split: Optional[int], rank: Optional[int] = None
    ) -> Tuple[int, Tuple[int, ...], Tuple[slice, ...]]:
        """Compute the (offset, local shape, slices) of one device's shard.

        The reference distributes ``size % nranks`` extra elements to the first
        ranks (communication.py:161-218).  GSPMD instead uses even
        ``ceil(n/N)`` chunks with the trailing shards truncated (possibly to
        zero); we follow the hardware so that metadata matches the actual
        layout of every ``jax.Array``.
        """
        if split is None:
            return 0, tuple(shape), tuple(slice(0, end) for end in shape)
        rank = 0 if rank is None else int(rank)
        nranks = self.size
        dims = len(shape)
        split = split % dims if dims else 0
        size = shape[split]
        per = _ceil_div(size, nranks) if size > 0 else 0
        start = min(rank * per, size)
        end = min((rank + 1) * per, size)
        lshape = list(shape)
        lshape[split] = end - start
        slices = tuple(
            slice(start, end) if i == split else slice(0, shape[i]) for i in range(dims)
        )
        return start, tuple(lshape), slices

    def lshape_map(self, shape: Tuple[int, ...], split: Optional[int]) -> np.ndarray:
        """(size, ndim) matrix of per-device shard shapes (reference:
        DNDarray.create_lshape_map, dndarray.py:598-629)."""
        n = self.size
        out = np.empty((n, max(len(shape), 1)), dtype=np.int64)
        for r in range(n):
            _, lshape, _ = self.chunk(shape, split, rank=r)
            out[r, : len(shape)] = lshape
        if len(shape) == 0:
            out = np.zeros((n, 0), dtype=np.int64)
        return out

    def counts_displs_shape(
        self, shape: Tuple[int, ...], axis: int
    ) -> Tuple[Tuple[int, ...], Tuple[int, ...], Tuple[int, ...]]:
        """Per-rank counts and displacements along ``axis``
        (reference: communication.py:220-248)."""
        counts, displs = [], []
        for r in range(self.size):
            off, lshape, _ = self.chunk(shape, axis, rank=r)
            counts.append(lshape[axis])
            displs.append(off)
        out_shape = list(shape)
        out_shape[axis] = -1
        return tuple(counts), tuple(displs), tuple(out_shape)

    # -------------------------------------------------------------- shardings
    def spec(self, split: Optional[int], ndim: int) -> PartitionSpec:
        """PartitionSpec placing mesh axis ``split_axis`` at dim ``split``."""
        if split is None or ndim == 0:
            return PartitionSpec()
        split = split % ndim
        parts: List[Optional[str]] = [None] * ndim
        parts[split] = self.split_axis
        return PartitionSpec(*parts)

    def sharding(self, split: Optional[int], ndim: int) -> NamedSharding:
        """NamedSharding for a DNDarray of ``ndim`` dims split at ``split``."""
        return NamedSharding(self.mesh, self.spec(split, ndim))

    def replicated(self, ndim: int) -> NamedSharding:
        return NamedSharding(self.mesh, PartitionSpec())

    # --------------------------------------------------------------- factory
    def _submesh(self, indices) -> "MeshComm":
        """New MeshComm over the given positions along the split axis (other
        mesh axes are preserved)."""
        if not len(indices):
            raise ValueError("sub-communicator needs at least one device")
        axis_pos = self.mesh.axis_names.index(self.split_axis)
        devices = np.take(self.mesh.devices, np.asarray(indices), axis=axis_pos)
        return MeshComm(Mesh(devices, self.mesh.axis_names), split_axis=self.split_axis)

    def Split(self, color: int = 0, key: int = 0) -> "MeshComm":
        """Sub-communicator creation (reference: communication.py:470-481).

        MPI semantics restated for a single controller: the split-axis
        positions are partitioned into color groups, and the result is one
        group's communicator over a sub-mesh of its devices.

        * scalar ``color`` — the common MPI idiom where every member passes
          the same value: returns a fresh communicator over all split-axis
          devices.
        * sequence ``color`` (one entry per split-axis position) — returns
          the group containing position ``key``.  (MPI gives every rank its
          own group; a single controller must name one — ``key`` doubles as
          that perspective.  Use :meth:`split_groups` for all groups at
          once; within a group, device order is preserved.)
        """
        colors = np.asarray(color)
        if colors.ndim == 0:
            return self._submesh(list(range(self.size)))
        if colors.shape != (self.size,):
            raise ValueError(
                f"per-device colors must have shape ({self.size},), got {colors.shape}"
            )
        key = int(key)
        if not 0 <= key < self.size:
            # MPI's key is an intra-group ordering hint; here it selects
            # the perspective position, so a silent modulo wrap would pick
            # an arbitrary group for MPI-ported `key=rank`-style values
            # (advisor round 2).  Reject instead.
            raise ValueError(
                f"key must be a split-axis position in [0, {self.size}), got {key}; "
                "use split_groups() for all groups at once"
            )
        mine = colors[key]
        return self._submesh([i for i in range(self.size) if colors[i] == mine])

    def split_groups(self, colors) -> dict:
        """All color-group sub-communicators at once: ``{color: MeshComm}``
        (the single-controller face of MPI's per-rank ``Split``)."""
        colors = np.asarray(colors)
        if colors.shape != (self.size,):
            raise ValueError(
                f"per-device colors must have shape ({self.size},), got {colors.shape}"
            )
        return {
            c: self._submesh([i for i in range(self.size) if colors[i] == c])
            for c in np.unique(colors).tolist()
        }


# ---------------------------------------------------------------------- world
_world_comm: Optional[MeshComm] = None
_default_comm: Optional[MeshComm] = None


def world() -> MeshComm:
    """The all-device communication context (reference: MPI_WORLD,
    communication.py:1909).  Fixed once created: narrowing the *default*
    context via :func:`use_comm` never changes what ``world()`` returns,
    just as MPI.COMM_WORLD is unaffected by the reference's ``use_comm``."""
    global _world_comm
    if _world_comm is None:
        _world_comm = MeshComm()
    return _world_comm


def get_comm() -> MeshComm:
    """Return the current default context (reference: communication.py:1927).
    Starts as :func:`world`; redirected by :func:`use_comm`."""
    return _default_comm if _default_comm is not None else world()


def use_comm(comm: Optional[MeshComm] = None) -> None:
    """Set the default context (reference: communication.py:1950)."""
    global _default_comm
    if comm is not None and not isinstance(comm, MeshComm):
        raise TypeError(f"comm must be a MeshComm, got {type(comm)}")
    _default_comm = comm


def sanitize_comm(comm: Optional[Communication]) -> MeshComm:
    """Validate-or-default a communication context (reference:
    communication.py:1933-1947)."""
    if comm is None:
        return get_comm()
    if isinstance(comm, MeshComm):
        return comm
    raise TypeError(f"comm must be None or a MeshComm, got {type(comm)}")


def local_mesh(n: Optional[int] = None, axis: str = SPLIT_AXIS) -> MeshComm:
    """Build a MeshComm over the first ``n`` devices (testing helper)."""
    devices = jax.devices()
    if n is not None:
        devices = devices[:n]
    return MeshComm(Mesh(np.array(devices), (axis,)), split_axis=axis)


def init_distributed(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
    **kwargs,
) -> Tuple[int, int]:
    """Multi-host bootstrap (the reference's ``mpirun`` + import-time
    ``MPI_WORLD`` creation, heat/core/communication.py:1909-1921).

    Wraps ``jax.distributed.initialize`` so user scripts stay launcher
    agnostic:

    * already initialized → no-op;
    * explicit arguments → passed straight through (errors propagate: the
      caller asked for a specific topology and should hear when it fails);
    * no arguments → delegate to JAX's own cluster auto-detection (Slurm,
      Open MPI, GCE TPU metadata, GKE env, ``JAX_COORDINATOR_ADDRESS``);
      when no cluster is detectable — a plain single-process run — this is
      a clean no-op rather than an error.

    Call it before any other JAX usage (backend initialization pins the
    process topology); called later in a single-process program it simply
    no-ops.  Returns ``(process_index, process_count)`` — the reference's
    ``(rank, size)``.
    """
    if not jax.distributed.is_initialized():
        explicit = (
            coordinator_address is not None
            or num_processes is not None
            or process_id is not None
            or bool(kwargs)
        )
        if explicit:
            jax.distributed.initialize(
                coordinator_address=coordinator_address,
                num_processes=num_processes,
                process_id=process_id,
                **kwargs,
            )
        else:
            from jax._src import xla_bridge as _xla_bridge

            if not _xla_bridge.backends_are_initialized():
                try:
                    # jax's ClusterEnv chain detects Slurm/MPI/GCE/GKE and
                    # reads JAX_COORDINATOR_ADDRESS itself
                    jax.distributed.initialize()
                except (ValueError, RuntimeError) as exc:
                    # "no cluster detected" is a clean single-process no-op;
                    # a cluster that WAS detected but failed to come up must
                    # fail loudly — silently degrading to N independent
                    # rank-0 jobs corrupts results
                    if _looks_multiprocess():
                        raise RuntimeError(
                            "a multi-process launcher environment was "
                            "detected but jax.distributed.initialize() "
                            f"failed: {exc}"
                        ) from exc
            elif _looks_multiprocess():
                import warnings

                warnings.warn(
                    "init_distributed() was called after the JAX backend was "
                    "initialized; multi-host setup was skipped although a "
                    "multi-process launcher environment is present. Call "
                    "init_distributed() before any other JAX usage.",
                    RuntimeWarning,
                    stacklevel=2,
                )
    return jax.process_index(), jax.process_count()


def _looks_multiprocess() -> bool:
    """Cheap launcher-env sniff: does this look like one process of many?"""

    def _int(name: str) -> int:
        # strict parse (envparse.env_int): a malformed launcher variable
        # must refuse to start, not silently come up single-process
        return envparse.env_int(name, 1)

    tpu_workers = os.environ.get("TPU_WORKER_HOSTNAMES", "")
    return (
        _int("SLURM_NTASKS") > 1
        or _int("OMPI_COMM_WORLD_SIZE") > 1
        or _int("PMI_SIZE") > 1
        or bool(os.environ.get("JAX_COORDINATOR_ADDRESS"))
        or len([w for w in tpu_workers.split(",") if w.strip()]) > 1
    )


def hybrid_mesh(
    ici: dict, dcn: Optional[dict] = None, *, process_is_granule: bool = False
) -> Mesh:
    """Build a DCN × ICI device mesh (the reference's two-tier topology —
    NCCL inside a node, MPI across, heat/optim/dp_optimizer.py:46 — expressed
    as mesh axes).

    Args:
        ici: ordered ``{axis_name: size}`` for axes riding intra-slice ICI
            links (fast: tensor/sequence/expert parallelism belong here).
        dcn: ordered ``{axis_name: size}`` for axes spanning the slow outer
            network (data parallelism, DASO's outer tier). Sizes of 1 are
            allowed and make the result a plain single-slice mesh.
        process_is_granule: what the dcn tier spans. ``False`` (default):
            TPU slices (`slice_index`) — multi-slice pods over DCN.
            ``True``: host processes — e.g. the hosts of one TPU slice, or
            any multi-host cluster whose devices carry no slice topology.

    Returns a ``jax.sharding.Mesh`` with dcn axes leading (slowest-varying),
    so collectives along ici axes never cross a granule boundary.

    >>> mesh = hybrid_mesh({"split": 4}, {"dp": 2})   # 2 slices x 4 chips
    >>> MeshComm(mesh)                                 # split rides ICI
    """
    from jax.experimental import mesh_utils

    dcn = dict(dcn or {})
    ici = dict(ici)
    if not ici:
        raise ValueError("ici must name at least one mesh axis")
    if set(dcn) & set(ici):
        raise ValueError(
            f"axis names must be distinct across tiers: {sorted(set(dcn) & set(ici))}"
        )
    names = tuple(dcn) + tuple(ici)
    dcn_shape = tuple(dcn.values())
    ici_shape = tuple(ici.values())
    n_dcn = int(np.prod(dcn_shape)) if dcn_shape else 1
    if n_dcn > 1:
        # create_hybrid_device_mesh wants rank-aligned shapes: dcn axes are
        # size 1 in the inner (ICI) shape and vice versa
        devices = mesh_utils.create_hybrid_device_mesh(
            mesh_shape=(1,) * len(dcn_shape) + ici_shape,
            dcn_mesh_shape=dcn_shape + (1,) * len(ici_shape),
            process_is_granule=process_is_granule,
        )
        return Mesh(devices, names)
    devices = mesh_utils.create_device_mesh(ici_shape)
    return Mesh(devices.reshape(dcn_shape + ici_shape), names)
