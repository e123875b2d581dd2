"""Read ``BENCHMARK.json`` and the data files it names.

The harness knows no cell, configuration or metric by name: whatever belongs
to one of them sits in a file that is found here from the manifest's entry.
"""

from __future__ import annotations

import importlib
import json
import os

PERF_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(PERF_DIR)


class ManifestError(Exception):
    """The manifest or a file it names is missing or inconsistent."""


def _read_json(path: str) -> dict:
    if not os.path.isfile(path):
        raise ManifestError(f"missing file {os.path.relpath(path, ROOT)}")
    with open(path) as fh:
        return json.load(fh)


def load_manifest() -> dict:
    return _read_json(os.path.join(ROOT, "BENCHMARK.json"))


def merged(base: dict, over: dict) -> dict:
    """``base`` with ``over`` laid on top, dictionaries merged key by key."""
    out = dict(base)
    for key, val in over.items():
        if isinstance(val, dict) and isinstance(out.get(key), dict):
            out[key] = merged(out[key], val)
        else:
            out[key] = val
    return out


def load_peaks(device_kind: str) -> dict:
    table = _read_json(os.path.join(PERF_DIR, "peaks.json"))
    if device_kind not in table["devices"]:
        raise ManifestError(
            f"device_kind {device_kind!r} is not in perf/peaks.json "
            f"(known: {sorted(table['devices'])}); add it with its source"
        )
    return table["devices"][device_kind]


def load_cell(manifest: dict, workload: str, rehearse: bool) -> dict:
    """Everything one run needs, as plain data.

    Returns ``{"cell", "config", "workload", "end_to_end", "per_layer"}``:
    the manifest's entry for the cell, its configuration file, its workload
    file (with the ``rehearse`` overrides of both applied when asked, and the
    traffic mix that the entry's ``traffic`` names laid in as ``traffic``),
    and the metrics that this cell reports.
    """
    cells = {c["name"]: c for c in manifest["workloads"]}
    if workload not in cells:
        raise ManifestError(
            f"no workload {workload!r} in BENCHMARK.json (has: {sorted(cells)})"
        )
    cell = cells[workload]
    configs = {c["name"]: c for c in manifest["configs"]}
    config = _read_json(os.path.join(ROOT, configs[cell["config"]]["file"]))
    wl = _read_json(os.path.join(PERF_DIR, "workloads", workload + ".json"))
    wl["traffic"] = _read_json(os.path.join(PERF_DIR, "traffic", cell["traffic"] + ".json"))
    if rehearse:
        config = merged(config, config.get("rehearse", {}))
        wl = merged(wl, wl.get("rehearse", {}))

    def reported(metric: dict) -> bool:
        return "workloads" not in metric or workload in metric["workloads"]

    end_to_end = [m for m in manifest["end_to_end"] if reported(m)]
    e2e_names = {m["name"] for m in end_to_end}
    per_layer = [
        m for m in manifest["per_layer"]
        if reported(m) and m["moves"] in e2e_names
    ]
    return {
        "cell": cell, "config": config, "workload": wl,
        "end_to_end": end_to_end, "per_layer": per_layer,
    }


def load_module(kind: str, name: str):
    """``perf/<kind>/<name>.py`` as a module; a missing one is an error."""
    if not os.path.isfile(os.path.join(PERF_DIR, kind, name + ".py")):
        raise ManifestError(f"missing file perf/{kind}/{name}.py")
    return importlib.import_module(f"perf.{kind}.{name}")
