"""Communication compat namespace (reference: heat/core/communication.py).

The reference's entire 1964-line MPI wrapper — dtype→MPI-type maps, derived
datatypes for strided buffers, forty explicit collectives — has no TPU
counterpart by design: collectives are jnp ops inside jit, compiled by XLA
onto ICI (see ``heat_tpu.parallel``).  What survives of the reference module
is its *context* surface, which lives in :mod:`heat_tpu.parallel.mesh`; this
module re-exports it under the reference's import path and names so that
``ht.core.communication.MPICommunication`` / ``ht.get_comm()`` /
``ht.MPI_WORLD`` resolve for code written against the reference
(communication.py:88, :120, :1909-1961).
"""

from __future__ import annotations

import jax

from . import telemetry
from ..parallel.mesh import (
    Communication,
    MeshComm,
    get_comm,
    local_mesh,
    sanitize_comm,
    use_comm,
    world,
)

__all__ = [
    "Communication",
    "MeshComm",
    "MPICommunication",
    "MPIRequest",
    "get_comm",
    "local_mesh",
    "sanitize_comm",
    "use_comm",
    "world",
]

#: compat alias: the reference's concrete backend class
#: (communication.py:120); on TPU the concrete backend is the mesh context.
MPICommunication = MeshComm


class MPIRequest:
    """Compat stand-in for the reference's nonblocking-handle wrapper
    (communication.py:29-85).  JAX dispatch is asynchronous already — every
    op returns immediately and ``wait`` drains the device queue."""

    def __init__(self, value=None):
        self.value = value

    def wait(self):
        if self.value is not None:
            with telemetry.sync("comm.wait"):  # MPIRequest.wait(): blocking is the contract
                jax.block_until_ready(self.value)
        return self.value

    Wait = wait


_self_comm = None


def __getattr__(name):
    # MPI_WORLD / MPI_SELF are created at import time in the reference
    # (communication.py:1909-1921); here they resolve lazily so importing the
    # library never touches the backend before the user configures it.
    if name == "MPI_WORLD":
        return world()
    if name == "MPI_SELF":
        # the reference's MPI_SELF is MPI.COMM_SELF — a size-1 communicator;
        # the faithful stand-in is a single-device mesh
        global _self_comm
        if _self_comm is None:
            _self_comm = local_mesh(1)
        return _self_comm
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
