"""Share of the HBM roofline that a block-diffusion model's key/value stream
reaches: the least seconds to read every key/value row its passes saw once
(``kv_rows_streamed``, layers x sessions x visible rows summed over every
pass, commits included, and ``row_bytes``, the bytes of a row of one layer,
both carried by the start of the newest ``lm.decode`` span:
``perf/work_models/sdar_decode.py:kv_stream``) at the published HBM peak,
over the device time under the scope ``ht.lm.block_attn``.  None where the
program has no such scope or span (a program without the layer)."""

import jax

from perf import manifest as mf
from perf import span_reduce
from perf.work_models import floor_seconds, sdar_decode

SPAN = "lm.decode"


def stream_shapes():
    """``{"kv_rows_streamed", "row_bytes"}`` of the newest decode span that
    streamed a block model's keys and values, or None."""
    try:
        from heat_tpu.core import telemetry
    except ImportError:
        return None
    begun = [e for e in telemetry.events("span_begin")
             if e.get("name") == SPAN and "kv_rows_streamed" in e and "row_bytes" in e]
    if not begun:
        return None
    return {"kv_rows_streamed": begun[-1]["kv_rows_streamed"], "row_bytes": begun[-1]["row_bytes"]}


def read(run):
    got = span_reduce.for_run(run)
    if not got or not got["calls"] or not got["scopes"].get("ht.lm.block_attn"):
        return None
    shapes = stream_shapes()
    if shapes is None:
        return None
    floor, _ = floor_seconds(sdar_decode.kv_stream(shapes),
                             mf.load_peaks(jax.devices()[0].device_kind))
    return 100.0 * floor * got["calls"] / got["scopes"]["ht.lm.block_attn"]
