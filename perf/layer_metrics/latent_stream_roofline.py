"""Share of the HBM roofline that the full latent read reaches: the least
seconds to read each session's visible rows once a layer and step (the rows
the newest decode call's stream visited, counted on the device and carried by
the end of its ``lm.decode`` span, at the ``latent_bytes`` a row its start
carries: ``perf/work_models/glm_decode.py:latent_stream``) at the published
HBM peak, over the device time under the scope ``ht.lm.latent_stream``.  None
where the program has no such scope or span (a program without the layer)."""

import jax

from perf import manifest as mf
from perf import span_reduce
from perf.work_models import floor_seconds, glm_decode

SPAN = "lm.decode"


def stream_shapes():
    """``{"latent_rows_streamed", "latent_bytes"}`` of the newest decode
    span that streamed, or None."""
    try:
        from heat_tpu.core import telemetry
    except ImportError:
        return None
    ended = [e for e in telemetry.events("span_end")
             if e.get("name") == SPAN and "latent_rows_streamed" in e]
    if not ended:
        return None
    begun = [e for e in telemetry.events("span_begin") if e.get("id") == ended[-1]["id"]]
    if not begun or "latent_bytes" not in begun[-1]:
        return None
    return {"latent_rows_streamed": ended[-1]["latent_rows_streamed"],
            "latent_bytes": begun[-1]["latent_bytes"]}


def read(run):
    got = span_reduce.for_run(run)
    if not got or not got["calls"] or not got["scopes"].get("ht.lm.latent_stream"):
        return None
    shapes = stream_shapes()
    if shapes is None:
        return None
    floor, _ = floor_seconds(glm_decode.latent_stream(shapes),
                             mf.load_peaks(jax.devices()[0].device_kind))
    return 100.0 * floor * got["calls"] / got["scopes"]["ht.lm.latent_stream"]
