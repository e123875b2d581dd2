"""Share of the HBM roofline that the state step of a power-retention model
reaches: the least seconds to read and write every layer's state once a step
(``perf/work_models/retention_state.py``: ``steps x layers x sessions x
key/value heads x`` the bytes the mathematics needs a head) at the published
HBM peak, over the device time under the scope ``ht.lm.retention_state``.

The shapes are those of the run: the newest ``lm.decode`` span in the
program's flight recorder says them, as ``shared_kv_attn_roofline`` reads its
own.  None where there is no such scope or no such span (a program without
the layer)."""

import jax

from perf import manifest as mf
from perf import span_reduce
from perf.work_models import floor_seconds, retention_state

SCOPE = "ht.lm.retention_state"
SPAN = "lm.decode"


def decode_shapes():
    """The attributes of the newest ``lm.decode`` span, or None."""
    try:
        from heat_tpu.core import telemetry
    except ImportError:
        return None
    spans = [e for e in telemetry.events("span_begin") if e.get("name") == SPAN]
    if not spans or not all(k in spans[-1] for k in retention_state.SHAPES):
        return None
    return {k: spans[-1][k] for k in retention_state.SHAPES}


def read(run):
    got = span_reduce.for_run(run)
    if not got or not got["calls"] or not got["scopes"].get(SCOPE):
        return None
    shapes = decode_shapes()
    if shapes is None:
        return None
    peaks = mf.load_peaks(jax.devices()[0].device_kind)
    floor, _ = floor_seconds(retention_state.step_work(**shapes), peaks)
    return 100.0 * floor * got["calls"] / got["scopes"][SCOPE]
