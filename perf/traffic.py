"""The one traffic generator: a workload file's ``traffic`` block to calls.

A mix is data.  ``traffic`` holds::

    {"loop": "closed", "clients": 1, "cycle": 96,
     "mix": [{"share": 1, "item": {"rows": 4096}}, ...]}

The generator lays out one cycle of ``cycle`` items in which every entry of
``mix`` appears in exact proportion to its ``share``, shuffles the cycle with
the seed, and repeats it for as long as the window lasts.  So every seed
offers the same set of calls in another order.  Each item also carries
``index`` (its position in the run) and ``u``, a uniform number in [0, 1)
drawn from the seed, for whatever the driver has to place (an offset, a
starting point).  Only a closed loop of one caller is generated today; the
keys are there so that an open loop is a new value, not a new format.
"""

from __future__ import annotations

import itertools

import numpy as np


def cycle_items(traffic: dict) -> list:
    """One cycle's items, unshuffled: exact shares, largest remainder."""
    if traffic.get("loop", "closed") != "closed" or traffic.get("clients", 1) != 1:
        raise ValueError("traffic: only a closed loop of one client is generated")
    mix = traffic["mix"]
    length = int(traffic.get("cycle", len(mix)))
    total = float(sum(e["share"] for e in mix))
    exact = [e["share"] / total * length for e in mix]
    counts = [int(x) for x in exact]
    by_remainder = sorted(range(len(mix)), key=lambda i: exact[i] - counts[i], reverse=True)
    for i in by_remainder[: length - sum(counts)]:
        counts[i] += 1
    items = []
    for entry, count in zip(mix, counts):
        items.extend(dict(entry["item"]) for _ in range(count))
    return items


def calls(traffic: dict, seed: int):
    """Endless iterator of items for one run."""
    base = cycle_items(traffic)
    rng = np.random.default_rng([int(seed), 0x7EA7])
    order = rng.permutation(len(base))
    for index in itertools.count():
        if index and index % len(base) == 0:
            order = rng.permutation(len(base))
        item = dict(base[order[index % len(base)]])
        item["index"] = index
        item["u"] = float(rng.random())
        yield item


def shapes(traffic: dict) -> list:
    """The distinct items of the mix: what set-up has to warm up."""
    seen, out = set(), []
    for entry in traffic["mix"]:
        key = tuple(sorted(entry["item"].items()))
        if key not in seen:
            seen.add(key)
            out.append(dict(entry["item"]))
    return out
