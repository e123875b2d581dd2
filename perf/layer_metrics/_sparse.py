"""What the readers of the sparse decode's scopes share: the shapes of the run
from the newest ``lm.decode`` span in the program's flight recorder, and a
part's share of the HBM roofline under its scope.  None where there is no such
scope or no such span (a program without the layer)."""

import jax

from perf import manifest as mf
from perf import span_reduce
from perf.work_models import floor_seconds, sparse_read

SPAN = "lm.decode"


def decode_shapes():
    """The attributes of the newest ``lm.decode`` span, or None."""
    try:
        from heat_tpu.core import telemetry
    except ImportError:
        return None
    spans = [e for e in telemetry.events("span_begin") if e.get("name") == SPAN]
    if not spans or not all(k in spans[-1] for k in sparse_read.SHAPES):
        return None
    return {k: spans[-1][k] for k in sparse_read.SHAPES}


def roofline(run, scope, part):
    """``part(shapes)``'s least seconds a call over the device time under
    ``scope``, in percent."""
    got = span_reduce.for_run(run)
    if not got or not got["calls"] or not got["scopes"].get(scope):
        return None
    shapes = decode_shapes()
    if shapes is None:
        return None
    peaks = mf.load_peaks(jax.devices()[0].device_kind)
    floor, _ = floor_seconds(part(shapes), peaks)
    return 100.0 * floor * got["calls"] / got["scopes"][scope]
