"""Failure detection and elastic restart-from-checkpoint (SURVEY.md §5).

The reference has no failure handling at all — "an MPI abort kills the job"
(SURVEY.md §5, failure detection row); its only recovery primitive is array
save/load.  This module supplies the subsystem TPU-first, building on the
sharded checkpoints of :mod:`heat_tpu.utils.checkpointing`:

* :func:`run_elastic` — a supervised training loop: every step's result is
  health-checked (non-finite loss/metrics count as failures, exceptions are
  caught), failures trigger a restore of the latest checkpoint and a rerun;
  deterministically-poisoned steps (a bad batch that fails again after
  restore) are skipped rather than retried forever; a restart budget bounds
  the total recovery work.
* :class:`StallDetector` — a wall-clock watchdog thread: if no heartbeat
  arrives within ``timeout`` seconds (a hung collective, a wedged host), a
  stall event fires.  XLA's static schedule removes data races, but a lost
  peer still hangs a collective forever — detection has to live on the host
  clock.
* :class:`FaultInjector` — deterministic fault injection for testing the
  above: raise at step N, or corrupt the loss to NaN at step N.  The test
  doctrine stays the reference's "no mocks" (SURVEY.md §4): injected faults
  run through the real restore path on the real mesh.  Round 8 extends it
  below the training loop: :meth:`~FaultInjector.oom_in` /
  :meth:`~FaultInjector.error_in` / :meth:`~FaultInjector.nan_in` /
  :meth:`~FaultInjector.stall_in` arm *sites* inside the transport engine
  (``transport.resplit`` / ``transport.take`` / ``transport.reshape``) and
  the fusion runner (``fusion.compile`` / ``fusion.exec``); installing the
  injector (:func:`install_injector` / :func:`injected`) wires it into the
  ``heat_tpu.core.guard`` hooks those subsystems consult on every attempt,
  so OOM backoff, eager fallback, and stall detection are all exercised by
  faults raised at their real call sites.  Round 20 adds the serving
  sites: ``serving.step`` (and ``serving.step.<engine>`` for one named
  fleet replica) is consulted by the serving worker before every batch,
  and ``serving.replica`` / ``serving.replica.<name>`` by the fleet
  router on every dispatch — so replica failover, circuit-open, and
  half-open-probe recovery are tested with real injected faults, not
  mocks.  A site key ending in ``.*`` arms every site under that prefix
  (``serving.step.*`` hits whichever replica flushes next).

Multi-host note: each host runs the same supervised loop SPMD-style; a
restore after a full-job restart resumes from the same sharded checkpoint
(``jax.distributed.initialize`` re-forms the mesh first).  In-place slice
shrink/grow is not attempted — XLA programs are compiled for a fixed mesh;
elasticity is restart-from-checkpoint onto the new mesh, which
:func:`heat_tpu.utils.checkpointing.load_checkpoint` supports via
``target`` shardings.
"""

from __future__ import annotations

import os
import threading
import time
from contextlib import contextmanager
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional

import jax
import numpy as np

from ..core import envparse, guard, memtrack, telemetry

__all__ = [
    "ElasticFailure",
    "FaultInjector",
    "InjectedOOM",
    "StallDetector",
    "clear_injector",
    "default_health_check",
    "injected",
    "install_injector",
    "run_elastic",
]


class InjectedOOM(RuntimeError):
    """Injected allocation failure.  The message deliberately carries the
    ``RESOURCE_EXHAUSTED`` marker so the transport engine's OOM matcher
    treats an injected failure exactly like a real XLA one — the backoff
    path under test is the production path, not a test double."""

    def __init__(self, site: str):
        super().__init__(f"RESOURCE_EXHAUSTED: injected OOM at {site}")
        self.site = site


class ElasticFailure(RuntimeError):
    """Raised when recovery is exhausted (restart budget spent)."""


class FaultInjector:
    """Deterministic fault injection for exercising the recovery path.

    >>> faults = FaultInjector().raise_at(5).nan_at(9)
    >>> loss = faults.fire(step, loss)   # call inside the step

    ``raise_at`` throws ``InjectedFault`` when the step executes;
    ``nan_at`` returns the loss corrupted to NaN instead.  Each fault
    fires once ("transient") unless ``sticky=True`` ("deterministic" —
    e.g. a poisoned batch that fails on every retry).

    Beyond the training loop, *site* injections target the guard hooks
    inside transport and fusion (see the module docstring).  A site fault
    fires on the next ``times`` hook consultations at that site and then
    disarms; every firing is appended to :attr:`fired`, so tests assert
    exactly what was injected where.  Arm sites, then install the injector
    (:func:`injected` scopes the installation)::

    >>> inj = FaultInjector(seed=0).oom_in("transport.resplit", times=1)
    >>> with injected(inj):
    ...     b = a.resplit(1)          # first tile attempt OOMs, backoff retries
    >>> assert inj.fired == [("oom", "transport.resplit")]
    """

    class InjectedFault(RuntimeError):
        pass

    def __init__(self, seed: Optional[int] = None):
        # seed defaults from HEAT_TPU_INJECT_SEED (CI pins it) and is
        # recorded for reproducibility bookkeeping; all injections are
        # count-deterministic, so equal seeds + equal arming = identical
        # fault schedules by construction.
        if seed is None:
            seed = envparse.env_int("HEAT_TPU_INJECT_SEED", 0, minimum=0)
        self.seed = int(seed)
        self._raises: Dict[int, bool] = {}
        self._nans: Dict[int, bool] = {}
        # site -> list of pending (kind, payload) faults, consumed FIFO
        self._sites: Dict[str, List[tuple]] = {}
        # simulated memory_stats() readings (see low_hbm): installed as
        # memtrack's stats override alongside the guard hooks
        self._mem_stats: Optional[List[dict]] = None
        self.fired: List[tuple] = []

    # ---------------------------------------------- site-level injection

    def _arm(self, site: str, kind: str, payload, times: int) -> "FaultInjector":
        queue = self._sites.setdefault(str(site), [])
        queue.extend([(kind, payload)] * int(times))
        return self

    def oom_in(self, site: str, *, times: int = 1) -> "FaultInjector":
        """Raise :class:`InjectedOOM` on the next ``times`` attempts at
        ``site`` (e.g. ``transport.resplit``)."""
        return self._arm(site, "oom", None, times)

    def error_in(
        self, site: str, *, times: int = 1, message: str = "injected failure"
    ) -> "FaultInjector":
        """Raise a generic ``InjectedFault`` at ``site`` — models an XLA
        compile/lowering bug (``fusion.compile``) or runtime error
        (``fusion.exec``)."""
        return self._arm(site, "error", str(message), times)

    def nan_in(self, site: str, *, times: int = 1) -> "FaultInjector":
        """Corrupt the value produced at ``site`` to NaN (inexact leaves
        only; sharding/layout preserved by in-place multiply)."""
        return self._arm(site, "nan", None, times)

    def stall_in(self, site: str, seconds: float, *, times: int = 1) -> "FaultInjector":
        """Sleep ``seconds`` at ``site`` — a wedged collective for
        :class:`StallDetector` to catch."""
        return self._arm(site, "stall", float(seconds), times)

    def low_hbm(
        self,
        free_bytes: int,
        *,
        limit: Optional[int] = None,
        devices: int = 1,
    ) -> "FaultInjector":
        """Simulate a memory-starved device: while this injector is
        installed, :func:`memtrack.min_free_bytes` reports ``free_bytes``
        of headroom (per device).  Pairs with :meth:`oom_in` to drive the
        informed OOM backoff on backends with no real ``memory_stats()``
        (CPU CI): the first retry sizes its tile from this budget instead
        of blind halving."""
        free = int(free_bytes)
        lim = int(limit) if limit is not None else max(2 * free, free + 1)
        self._mem_stats = [
            {
                "device": f"injected:{i}",
                "bytes_limit": lim,
                "bytes_in_use": lim - free,
            }
            for i in range(max(int(devices), 1))
        ]
        return self

    def _pending(self, site: str) -> Optional[List[tuple]]:
        """Armed queue for ``site``: exact match first, then a prefix
        wildcard — arming ``"serving.step.*"`` fires for any
        replica-scoped site (``serving.step.r3``) so fleet tests target
        one replica or all of them without enumerating engine names."""
        queue = self._sites.get(site)
        if queue:
            return queue
        for key, pending in self._sites.items():
            if key.endswith(".*") and pending and site.startswith(key[:-1]):
                return pending
        return None

    def fire_site(self, site: str) -> None:
        """Hook target for :func:`heat_tpu.core.guard.fire`."""
        queue = self._pending(site)
        if not queue or queue[0][0] not in ("oom", "error", "stall"):
            return
        kind, payload = queue.pop(0)
        self.fired.append((kind, site))
        if kind == "oom":
            raise InjectedOOM(site)
        if kind == "error":
            raise FaultInjector.InjectedFault(f"{payload} at {site}")
        time.sleep(payload)  # stall

    def corrupt_site(self, site: str, value):
        """Hook target for :func:`heat_tpu.core.guard.corrupt`."""
        queue = self._pending(site)
        if not queue or queue[0][0] != "nan":
            return value
        queue.pop(0)
        self.fired.append(("nan", site))

        def poison(x):
            dt = np.dtype(getattr(x, "dtype", np.float64))
            if np.issubdtype(dt, np.inexact):
                return x * dt.type(np.nan)
            return x

        return jax.tree_util.tree_map(poison, value)

    # -------------------------------------------- step-level injection

    def raise_at(self, step: int, *, sticky: bool = False) -> "FaultInjector":
        self._raises[int(step)] = sticky
        return self

    def nan_at(self, step: int, *, sticky: bool = False) -> "FaultInjector":
        self._nans[int(step)] = sticky
        return self

    def fire(self, step: int, loss):
        step = int(step)
        if step in self._raises:
            if not self._raises[step]:
                del self._raises[step]
            raise FaultInjector.InjectedFault(f"injected fault at step {step}")
        if step in self._nans:
            if not self._nans[step]:
                del self._nans[step]
            return jax.tree_util.tree_map(
                lambda x: np.asarray(x, dtype=np.float32) * np.nan, loss
            )
        return loss


class StallDetector:
    """Host-clock watchdog: fires ``on_stall`` if :meth:`beat` goes quiet.

    >>> watchdog = StallDetector(timeout=300, on_stall=callback)
    >>> watchdog.start()
    >>> for batch in data:
    ...     watchdog.beat()   # after each completed step
    >>> watchdog.stop()

    The callback runs on the watchdog thread; it should record/alert and
    leave process teardown to the supervisor (killing a wedged XLA
    collective from inside the process is not recoverable anyway).

    :meth:`pause` suspends the watchdog for work that is legitimately
    quiet — the first compile of a large fused chain can exceed any sane
    collective timeout.  It nests, and works standalone or scoped::

    >>> with watchdog.pause():
    ...     out = chain.materialize()   # long XLA compile, no heartbeat

    :meth:`subscribe` registers push callbacks ``cb(kind, info)`` with
    kind ∈ ``{"stall", "recover", "pause", "resume"}`` — the serving
    admission gate rides this instead of polling.  ``"recover"`` fires
    on the first beat after a stall fired.  Callbacks run on whichever
    thread triggered the transition (watchdog thread for ``"stall"``)
    and are dispatched from a snapshot taken under the lock, so a
    subscriber may unsubscribe itself (or others) mid-dispatch.
    """

    def __init__(self, timeout: float, on_stall: Optional[Callable[[float], None]] = None):
        self.timeout = float(timeout)
        self.on_stall = on_stall
        self._last = time.monotonic()
        self._stop = threading.Event()
        self._fired = False
        self._paused = 0
        # one lock for ALL of _last/_fired/_paused/_subs: beat() and the
        # watcher's check-and-fire used to race unlocked, so a beat
        # landing between the quiet check and `_fired = True` could be
        # swallowed by a stale stall (pinned in tests/test_fault.py)
        self._pause_lock = threading.Lock()
        self._subs: List[Callable[[str, dict], None]] = []
        self._thread: Optional[threading.Thread] = None

    def start(self) -> "StallDetector":
        self._last = time.monotonic()
        self._thread = threading.Thread(target=self._watch, daemon=True)
        self._thread.start()
        return self

    def subscribe(self, callback: Callable[[str, dict], None]) -> Callable[[str, dict], None]:
        """Register ``callback(kind, info)`` for stall-plane transitions."""
        with self._pause_lock:
            if callback not in self._subs:
                self._subs.append(callback)
        return callback

    def unsubscribe(self, callback: Callable[[str, dict], None]) -> None:
        """Remove a subscriber; unknown callbacks are a no-op."""
        with self._pause_lock:
            try:
                self._subs.remove(callback)
            except ValueError:
                pass

    def _notify(self, kind: str, **info) -> None:
        # snapshot under the lock, dispatch outside it: subscribers may
        # re-enter subscribe/unsubscribe (or beat()) without deadlock
        with self._pause_lock:
            subs = tuple(self._subs)
        for callback in subs:
            try:
                callback(kind, dict(info))
            except Exception as exc:  # noqa: BLE001 — watchdog must survive
                telemetry.record_event(
                    "stall_subscriber_error", kind=kind, error=repr(exc)
                )

    def beat(self) -> None:
        with self._pause_lock:
            recovered = self._fired
            self._last = time.monotonic()
            self._fired = False
        # a stall postmortem reads the last heartbeats (and the spans
        # open around them) straight out of the flight recorder
        telemetry.record_event("heartbeat")
        if recovered:
            self._notify("recover", timeout_s=self.timeout)

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=self.timeout + 1.0)

    def pause(self) -> "_StallPause":
        """Suspend stall detection; nests.  Returns a context manager
        whose exit calls :meth:`resume` — or call :meth:`resume`
        yourself for the standalone form."""
        with self._pause_lock:
            self._paused += 1
            depth = self._paused
        telemetry.record_event("stall_pause", depth=depth)
        self._notify("pause", depth=depth)
        return _StallPause(self)

    def resume(self) -> None:
        """Undo one :meth:`pause`; re-arms the clock when the last pause
        lifts, so paused time never counts as quiet time."""
        with self._pause_lock:
            # re-arm the clock *before* lifting the pause flag: the watch
            # thread must never pair a lifted flag with a stale _last
            # from before the pause
            self._last = time.monotonic()
            self._fired = False
            self._paused = max(0, self._paused - 1)
            depth = self._paused
        telemetry.record_event("stall_resume", depth=depth)
        self._notify("resume", depth=depth)

    def _watch(self) -> None:
        poll = min(0.05, self.timeout / 4)
        while not self._stop.wait(poll):
            with self._pause_lock:
                # check-and-fire under the same lock beat() writes under:
                # a concurrent beat either lands before the check (no
                # fire) or after the fire (a "recover"), never in between
                if self._paused:
                    continue
                quiet = time.monotonic() - self._last
                if quiet <= self.timeout or self._fired:
                    continue
                self._fired = True  # once per stall, not once per poll
            # recorded from the watchdog thread: open_spans() reaches
            # across threads, so the event names what the workload had
            # in flight when it went quiet
            telemetry.record_event(
                "stall",
                quiet_s=round(quiet, 3),
                timeout_s=self.timeout,
                open_spans=telemetry.open_spans(),
            )
            telemetry.postmortem("stall")
            if self.on_stall is not None:
                self.on_stall(quiet)
            self._notify("stall", quiet_s=round(quiet, 3), timeout_s=self.timeout)


class _StallPause:
    """Context-manager half of :meth:`StallDetector.pause` — the pause is
    already taken when this object exists; exit releases it."""

    def __init__(self, detector: StallDetector):
        self._detector = detector

    def __enter__(self) -> StallDetector:
        return self._detector

    def __exit__(self, *exc) -> bool:
        self._detector.resume()
        return False


# ------------------------------------------------ injector installation
# The guard hooks (heat_tpu.core.guard.fire/corrupt) are consulted on
# every transport tile attempt and fused execution; installing an
# injector arms them process-wide.


def install_injector(injector: FaultInjector) -> FaultInjector:
    """Arm the guard hooks with ``injector`` (process-wide); an injector
    carrying :meth:`~FaultInjector.low_hbm` stats also installs them as
    memtrack's device-stats override."""
    guard._INJECTOR = injector
    if injector._mem_stats is not None:
        memtrack.set_stats_override(injector._mem_stats)
    return injector


def clear_injector() -> None:
    """Disarm the guard hooks (and any simulated memory stats)."""
    guard._INJECTOR = None
    memtrack.set_stats_override(None)


@contextmanager
def injected(injector: FaultInjector):
    """Scoped :func:`install_injector`::

    >>> with injected(FaultInjector().oom_in("transport.resplit")):
    ...     b = a.resplit(1)
    """
    prev = guard._INJECTOR
    guard._INJECTOR = injector
    has_mem = injector._mem_stats is not None
    prev_mem = (
        memtrack.set_stats_override(injector._mem_stats) if has_mem else None
    )
    try:
        yield injector
    finally:
        guard._INJECTOR = prev
        if has_mem:
            memtrack.set_stats_override(prev_mem)


def default_health_check(metrics: Any) -> bool:
    """Healthy iff every array/scalar leaf of ``metrics`` is finite.

    ``np.inexact`` covers real *and* complex floats —
    ``issubdtype(complex64, floating)`` is False, and a NaN hiding in a
    complex metric (an FFT diagnostic, say) is exactly as fatal as a real
    one.
    """
    for leaf in jax.tree_util.tree_leaves(metrics):
        arr = np.asarray(leaf)
        if np.issubdtype(arr.dtype, np.inexact) and not np.isfinite(arr).all():
            return False
    return True


@dataclass
class ElasticReport:
    """What happened during a :func:`run_elastic` run."""

    steps_run: int = 0
    restarts: int = 0
    skipped_steps: List[int] = field(default_factory=list)
    events: List[Dict[str, Any]] = field(default_factory=list)

    def record(self, kind: str, **info) -> None:
        self.events.append({"kind": kind, **info})


def run_elastic(
    step_fn: Callable[[Any, Any], tuple],
    init_state: Any,
    batch_fn: Callable[[int], Any],
    n_steps: int,
    *,
    checkpointer=None,
    checkpoint_every: int = 50,
    max_restarts: int = 3,
    health_check: Callable[[Any], bool] = default_health_check,
    on_event: Optional[Callable[[Dict[str, Any]], None]] = None,
    on_step: Optional[Callable[[int, Any], None]] = None,
):
    """Run ``n_steps`` of training under failure supervision.

    Args:
        step_fn: ``(state, batch) -> (state, metrics)``; exceptions and
            non-finite metrics are treated as step failures.
        init_state: starting state (any pytree the checkpointer can save).
        batch_fn: ``step -> batch``; called once per attempted step, so
            data order is reproducible across restarts.
        n_steps: total steps to run.
        checkpointer: a :class:`heat_tpu.utils.checkpointing.Checkpointer`;
            ``None`` recovers by rewinding to ``init_state`` (step 0).
        checkpoint_every: save cadence in steps (ignored without a
            checkpointer).
        max_restarts: recovery budget; exceeding it raises
            :class:`ElasticFailure` carrying the report so far.
        health_check: predicate on the step's metrics; default = all
            float leaves finite.
        on_event: optional callback receiving each event dict as it is
            recorded (for logging/alerting).
        on_step: optional callback ``(step, metrics)`` after each
            *successful* step — the place to beat a
            :class:`StallDetector` or log progress.

    Returns:
        ``(state, report)`` — the final state and an :class:`ElasticReport`.

    A step that fails twice at the same index (fails again immediately
    after its restore) is deterministic — retrying cannot help, so the
    step is skipped and recorded in ``report.skipped_steps`` (the batch's
    contribution is lost; the alternative is an unbounded crash loop).
    The skip happens in place — the pre-step state is intact, so no
    restore is needed and the restart budget is not charged again.
    """

    def emit(report: ElasticReport, kind: str, **info) -> None:
        report.record(kind, **info)
        if on_event is not None:
            on_event(report.events[-1])

    report = ElasticReport()
    state = init_state
    step = 0
    last_saved = None
    last_failed_step = None

    if checkpointer is not None:
        restored = checkpointer.restore_latest(target={"state": init_state, "step": 0})
        if restored is not None:
            state, step = restored["state"], int(restored["step"])
            last_saved = step
            emit(report, "resume", step=step)

    while step < n_steps:
        if step in report.skipped_steps:
            step += 1
            continue
        try:
            new_state, metrics = step_fn(state, batch_fn(step))
            # surface device-side NaN/Inf (and deferred XLA errors) now,
            # while recovery is still possible
            with telemetry.sync("fault.health_check"):
                jax.block_until_ready(metrics)
            if not health_check(metrics):
                raise _UnhealthyStep(f"health check failed at step {step}")
        except Exception as exc:  # noqa: BLE001 — any step failure recovers
            if step == last_failed_step:
                # failed, restored, failed again at the same step: the
                # fault is deterministic in the (state, batch) pair — skip
                # it in place (the pre-step state is intact; no restore,
                # no extra budget charge)
                report.skipped_steps.append(step)
                emit(report, "skip", step=step, error=repr(exc))
                last_failed_step = None
                step += 1
                continue
            if report.restarts >= max_restarts:
                emit(report, "give_up", step=step, error=repr(exc))
                raise ElasticFailure(
                    f"restart budget ({max_restarts}) exhausted at step {step}: {exc!r}"
                ) from exc
            report.restarts += 1
            emit(report, "failure", step=step, error=repr(exc))
            last_failed_step = step
            restored = None
            if checkpointer is not None and last_saved is not None:
                restored = checkpointer.restore_latest(
                    target={"state": init_state, "step": 0}
                )
            if restored is not None:
                state, step = restored["state"], int(restored["step"])
                emit(report, "restore", step=step)
            else:
                # checkpoint dir cleaned or save half-failed: rewind to init
                state, step = init_state, 0
                emit(report, "rewind", step=0)
            continue

        state = new_state
        step += 1
        report.steps_run += 1
        if on_step is not None:
            on_step(step, metrics)
        if (
            checkpointer is not None
            and checkpoint_every > 0
            and step % checkpoint_every == 0
        ):
            checkpointer.save(step, {"state": state, "step": step})
            last_saved = step

    return state, report


class _UnhealthyStep(RuntimeError):
    pass
