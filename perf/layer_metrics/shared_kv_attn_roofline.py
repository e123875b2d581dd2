"""Share of the HBM roofline that the reads of the shared key/value cache
reach: the least seconds to read ``steps x readers x sessions x context x
token_bytes`` a call (``perf/work_models/shared_kv_read.py``) at the published
HBM peak, over the device time under the scope ``ht.lm.shared_kv_attn``.

The shapes are those of the run: the newest ``lm.decode`` span in the
program's flight recorder says them (``batch``, ``context``, ``steps``,
``readers``, ``token_bytes``), so any cell listed under this metric is held to
its own bytes.  (``perf/run.py`` hands a reader neither the configuration nor
the cell's name; the span is what the run can say.)  None where there is no
such scope or no such span."""

import jax

from perf import manifest as mf
from perf import span_reduce
from perf.work_models import floor_seconds, shared_kv_read

SCOPE = "ht.lm.shared_kv_attn"
SPAN = "lm.decode"


def decode_shapes():
    """The attributes of the newest ``lm.decode`` span, or None."""
    try:
        from heat_tpu.core import telemetry
    except ImportError:
        return None
    spans = [e for e in telemetry.events("span_begin") if e.get("name") == SPAN]
    if not spans or not all(k in spans[-1] for k in shared_kv_read.SHAPES):
        return None
    return {k: spans[-1][k] for k in shared_kv_read.SHAPES}


def read(run):
    got = span_reduce.for_run(run)
    if not got or not got["calls"] or not got["scopes"].get(SCOPE):
        return None
    shapes = decode_shapes()
    if shapes is None:
        return None
    peaks = mf.load_peaks(jax.devices()[0].device_kind)
    floor, _ = floor_seconds(shared_kv_read.read_work(**shapes), peaks)
    return 100.0 * floor * got["calls"] / got["scopes"][SCOPE]
