"""Drivers: the calling code for one kind of user-level call, one file each,
named by a workload's ``driver``.  A driver is the only place where the
benchmark touches the program (``import heat_tpu as ht`` arrives as
``ctx.ht``), and it touches it through the entry points a user calls.

    setup(ctx) -> state            data into DNDarrays, estimators fitted
    call(state, item) -> out       THE timed call; ``out`` is a dict whose
                                   device arrays the harness blocks on
    keep(state, item, out)         what ``check`` needs of this call
    release(state)                 drop the program's state (not the data)
    check(state, kept, ctx)        -> (numbers, info): every number compared
                                   with its limit, by the plain reference
    control(state, item, ctx)      -> an ``out`` like ``call``'s, made by the
                                   control: the reference in the program's
                                   place one precision lower, or the
                                   program's own lower-precision path

``ctx`` has ``ht``, ``config``, ``workload``, ``data`` (what the generator
made), ``seed``, ``chips``.
"""


class Arr:
    """A control's result dressed as the program's: ``larray`` and ``shape``."""

    def __init__(self, larray):
        self.larray = larray
        self.shape = tuple(larray.shape)
