"""Data-parallel neural network training (reference:
heat/nn/data_parallel.py, 378 LoC).

The reference wraps a ``torch.nn.Module`` and registers per-parameter backward
hooks that Allreduce gradients — blocking (:223-241) or non-blocking with
wait-handles finalized by forward pre-hooks one iteration later (:243-299).
On TPU that entire machinery collapses into **one jitted train step**: the
batch is sharded over the mesh, parameters are replicated, and XLA inserts a
single fused gradient all-reduce (and overlaps it with the backward pass —
the optimization the non-blocking hooks hand-build).  ``DataParallelMultiGPU``
(NCCL-in-node + MPI-across, :316-378) maps to the same step over a 2-axis
(dcn × ici) mesh; see :class:`heat_tpu.optim.DASO` for the delayed
cross-slice sync.
"""

from __future__ import annotations

import inspect
from typing import Any, Callable, Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..core.dndarray import DNDarray
from ..parallel.mesh import MeshComm, sanitize_comm

__all__ = ["DataParallel", "DataParallelMultiGPU"]


def _default_loss(logits, targets):
    if logits.shape == targets.shape and jnp.issubdtype(targets.dtype, jnp.floating):
        return jnp.mean((logits - targets) ** 2)
    # integer targets → softmax cross-entropy
    logp = jax.nn.log_softmax(logits, axis=-1)
    onehot = jax.nn.one_hot(targets, logits.shape[-1], dtype=logp.dtype)
    return -jnp.mean(jnp.sum(onehot * logp, axis=-1))


class DataParallel:
    """Data-parallel wrapper around a Flax module (reference:
    nn/data_parallel.py:21).

    API shape follows the reference — construct with a network, a
    communication context and an optimizer, then train — but the step is
    functional: ``loss = model.train_step(batch, targets)`` replaces the
    torch-style forward/backward/step triple, because on TPU the whole
    iteration must live inside one compiled program to fuse the collective.

    Parameters
    ----------
    module : flax.linen.Module
        The network.
    comm : MeshComm, optional
        Mesh context; the batch is sharded over its split axis.
    optimizer : heat_tpu.optim.DataParallelOptimizer, optional
        Wrapped optax optimizer.
    loss_fn : callable, optional
        ``loss_fn(logits, targets) -> scalar``. Defaults to cross-entropy for
        integer targets, MSE otherwise.
    blocking : bool
        Accepted for reference parity. Both modes compile to the same overlap
        schedule under XLA (the non-blocking hand-overlap is automatic).
    """

    def __init__(
        self,
        module: Any,
        comm: Optional[MeshComm] = None,
        optimizer: Optional[Any] = None,
        loss_fn: Optional[Callable] = None,
        blocking: bool = True,
        blocking_parameter_updates: Optional[bool] = None,
    ):
        if blocking_parameter_updates is not None:
            # the reference's keyword spelling (data_parallel.py:52)
            blocking = blocking_parameter_updates
        self.module = module
        self.blocking = blocking
        self.comm = sanitize_comm(comm)
        self.optimizer = optimizer
        self.loss_fn = loss_fn
        self.params = None
        self._mesh = self.comm.mesh
        self._batch_sharding = NamedSharding(self._mesh, P(self.comm.split_axis))
        self._replicated = NamedSharding(self._mesh, P())
        self._train_step = None
        self._apply = None
        if optimizer is not None and hasattr(optimizer, "_bind_model"):
            optimizer._bind_model(self)

    # ------------------------------------------------------------------ init
    def init(self, rngs, sample_input) -> "DataParallel":
        """Initialize parameters, replicated across the mesh.

        The reference seeds every rank identically and resets parameters
        (data_parallel.py:107-109) to guarantee replica-identical init; a
        single replicated variable tree gives the same guarantee by
        construction.
        """
        from ..optim.dp_optimizer import DASO

        if isinstance(self.optimizer, DASO):
            raise TypeError(
                "DASO requires the two-tier step: use DataParallelMultiGPU"
            )
        variables = self._init_variables(rngs, sample_input)
        self.variables = jax.device_put(variables, self._replicated)
        self.params = self.variables.get("params", self.variables)
        if self.optimizer is not None:
            self.optimizer.init(self.params)
        return self

    def _init_variables(self, rngs, sample_input):
        """Module init + call-signature probing shared by both wrappers."""
        if isinstance(rngs, int):
            rngs = jax.random.PRNGKey(rngs)
        sample = (
            sample_input.larray
            if isinstance(sample_input, DNDarray)
            else jnp.asarray(sample_input)
        )
        variables = self.module.init(rngs, sample)
        call_params = inspect.signature(self.module.__call__).parameters
        self._accepts_train = "train" in call_params
        self._has_batch_stats = "batch_stats" in variables
        return variables

    def _build_loss_grads(self):
        """Return ``f(variables, b, t) -> (loss, updated_collections, grads)``
        — the forward/backward core shared by the flat DP step and the
        vmapped DASO slice step."""
        loss_fn = self.loss_fn
        has_bn = self._has_batch_stats
        train_kw = {"train": True} if self._accepts_train else {}

        def loss_grads(variables, b, t):
            params = variables["params"]
            rest = {k: v for k, v in variables.items() if k != "params"}

            def loss_of(p):
                v = {"params": p, **rest}
                if has_bn:
                    logits, updated = self.module.apply(
                        v, b, mutable=["batch_stats"], **train_kw
                    )
                else:
                    logits, updated = self.module.apply(v, b, **train_kw), {}
                return (loss_fn or _default_loss)(logits, t), updated

            (loss, updated), grads = jax.value_and_grad(loss_of, has_aux=True)(params)
            return loss, updated, grads

        return loss_grads

    # --------------------------------------------------------------- forward
    def __call__(self, x):
        """Forward pass with the batch sharded over the mesh."""
        if self.params is None:
            raise RuntimeError("call .init(rng, sample_input) first")
        xv = x.larray if isinstance(x, DNDarray) else jnp.asarray(x)
        xv = jax.device_put(xv, self._batch_sharding)
        if self._apply is None:
            self._apply = jax.jit(lambda v, b: self.module.apply(v, b))
        out = self._apply(self.variables, xv)
        if isinstance(x, DNDarray):
            from ..core import types
            from ..core.dndarray import _ensure_split

            wrapped = DNDarray(
                out, tuple(out.shape), types.canonical_heat_type(out.dtype),
                0, x.device, x.comm,
            )
            return _ensure_split(wrapped, 0)
        return out

    def forward(self, x):
        """Reference keyword for the forward pass (data_parallel.py's
        torch-module spelling); identical to calling the wrapper."""
        return self(x)

    # ------------------------------------------------------------ train step
    def train_step(self, batch, targets):
        """One fused DP training iteration: forward, backward, gradient
        all-reduce (implicit psum over the mesh), optimizer update.

        Returns the loss as a 0-d device scalar so back-to-back steps
        pipeline (a per-step host readback would serialize them);
        ``float(loss)`` blocks when the value is needed."""
        if self.params is None:
            raise RuntimeError("call .init(rng, sample_input) first")
        if self.optimizer is None:
            raise RuntimeError("no optimizer attached")
        bv = batch.larray if isinstance(batch, DNDarray) else jnp.asarray(batch)
        tv = targets.larray if isinstance(targets, DNDarray) else jnp.asarray(targets)
        bv = jax.device_put(bv, self._batch_sharding)
        tv = jax.device_put(tv, self._batch_sharding)

        if self._train_step is None:
            tx = self.optimizer.tx
            loss_grads = self._build_loss_grads()

            import optax

            def step(variables, opt_state, b, t):
                loss, updated, grads = loss_grads(variables, b, t)
                params = variables["params"]
                rest = {k: v for k, v in variables.items() if k != "params"}
                updates, new_state = tx.update(grads, opt_state, params)
                new_params = optax.apply_updates(params, updates)
                new_variables = {"params": new_params, **rest, **updated}
                return new_variables, new_state, loss

            self._train_step = jax.jit(
                step,
                out_shardings=(self._replicated, self._replicated, self._replicated),
            )

        self.variables, self.optimizer.state, loss = self._train_step(
            self.variables, self.optimizer.state, bv, tv
        )
        self.params = self.variables.get("params", self.variables)
        return loss


class DataParallelMultiGPU(DataParallel):
    """Two-tier data parallelism (reference: data_parallel.py:316-378 — NCCL
    inside the node, MPI across).

    On TPU both tiers are mesh axes.  With a plain optimizer this is identical
    to :class:`DataParallel` (XLA reduces gradients over the whole mesh).
    With a :class:`heat_tpu.optim.DASO` optimizer the step becomes the
    reference's hierarchical scheme: every parameter leaf carries a leading
    ``n_slices`` dim sharded over the DCN axis, the train step is vmapped over
    it (so gradient reductions stay intra-slice, on ICI), and the cross-slice
    parameter average runs only when DASO's skip logic says so — one DCN
    all-reduce per skip window instead of per step (reference: _global_sync
    gating, heat/optim/dp_optimizer.py:432).
    """

    def __init__(self, module, comm=None, optimizer=None, loss_fn=None):
        super().__init__(module, comm=comm, optimizer=optimizer, loss_fn=loss_fn)

    def _daso(self):
        from ..optim.dp_optimizer import DASO

        return self.optimizer if isinstance(self.optimizer, DASO) else None

    def init(self, rngs, sample_input) -> "DataParallelMultiGPU":
        daso = self._daso()
        if daso is None:
            return super().init(rngs, sample_input)
        variables = self._init_variables(rngs, sample_input)
        # slice-stacked layout: leading n_slices dim over DCN, replicated on ICI
        self.variables = daso.stack_tree(variables)
        self.params = self.variables.get("params", self.variables)
        daso.init(self.params)
        return self

    def __call__(self, x):
        daso = self._daso()
        if daso is None:
            return super().__call__(x)
        if self.params is None:
            raise RuntimeError("call .init(rng, sample_input) first")
        # inference uses the slice-averaged model — between syncs this is the
        # "global" model DASO's next sync would produce (reference: inference
        # happens after _global_sync, dp_optimizer.py:432)
        saved = self.variables
        try:
            self.variables = jax.tree.map(
                lambda v: (
                    jnp.mean(v, axis=0).astype(v.dtype)
                    if jnp.issubdtype(v.dtype, jnp.floating)
                    else v[0]
                ),
                saved,
            )
            return super().__call__(x)
        finally:
            self.variables = saved

    def train_step(self, batch, targets):
        daso = self._daso()
        if daso is None:
            return super().train_step(batch, targets)
        if self.params is None:
            raise RuntimeError("call .init(rng, sample_input) first")
        n = daso.n_slices
        bv = batch.larray if isinstance(batch, DNDarray) else jnp.asarray(batch)
        tv = targets.larray if isinstance(targets, DNDarray) else jnp.asarray(targets)
        if bv.shape[0] % n:
            raise ValueError(f"batch size {bv.shape[0]} not divisible by {n} slices")
        # (B, ...) → (n_slices, B/n, ...): slice dim on DCN, batch dim on ICI
        bv = bv.reshape((n, -1) + bv.shape[1:])
        tv = tv.reshape((n, -1) + tv.shape[1:])
        mesh = daso.mesh
        ici = self.comm.split_axis

        def two_tier(x):
            # slice dim over DCN (absent on 1-axis meshes), batch dim over ICI
            spec = P(*((daso.dcn_axis, ici) + (None,) * (x.ndim - 2)))
            return jax.device_put(x, NamedSharding(mesh, spec))

        bv, tv = two_tier(bv), two_tier(tv)

        if self._train_step is None:
            tx = daso.tx
            slice_grads = self._build_loss_grads()

            import optax

            def step(variables, opt_state, b, t):
                # vmap over the slice dim: per-slice forward/backward with
                # per-slice parameters; the elementwise optax update then
                # advances every slice's state independently
                loss, updated, grads = jax.vmap(slice_grads)(variables, b, t)
                params = variables["params"]
                rest = {k: v for k, v in variables.items() if k != "params"}
                updates, new_state = tx.update(grads, opt_state, params)
                new_params = optax.apply_updates(params, updates)
                new_variables = {"params": new_params, **rest, **updated}
                return new_variables, new_state, jnp.mean(loss)

            self._train_step = jax.jit(step)

        self.variables, daso.state, loss = self._train_step(
            self.variables, daso.state, bv, tv
        )
        daso.batches_seen += 1
        if daso.should_sync_globally():
            if daso._sync_fn is None:
                daso._build_sync(self.variables)
            self.variables = daso._sync_fn(self.variables)
        self.params = self.variables.get("params", self.variables)
        return loss
