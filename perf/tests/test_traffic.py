"""The traffic generator: same set of calls for every seed, another order."""

from perf import manifest as mf
from perf import traffic as tf


BLOCKS = {"loop": "closed", "clients": 1, "cycle": 96, "mix": [
    {"share": 1, "item": {"rows": 4096}}, {"share": 1, "item": {"rows": 16384}},
    {"share": 1, "item": {"rows": 65536}}]}


def test_every_cell_names_a_traffic_file():
    manifest = mf.load_manifest()
    for cell in manifest["workloads"]:
        wl = mf.load_cell(manifest, cell["name"], False)["workload"]
        assert tf.cycle_items(wl["traffic"]) and tf.shapes(wl["traffic"])


def test_exact_shares_and_seeded_order():
    wl = BLOCKS
    assert sorted(s["rows"] for s in tf.shapes(wl)) == [4096, 16384, 65536]

    def first_cycle(seed):
        gen = tf.calls(wl, seed)
        return [next(gen) for _ in range(wl["cycle"])]

    a, b, a2 = first_cycle(2**31 + 5), first_cycle(7), first_cycle(2**31 + 5)
    for cyc in (a, b):
        counts = {}
        for item in cyc:
            counts[item["rows"]] = counts.get(item["rows"], 0) + 1
            assert 0.0 <= item["u"] < 1.0
        assert set(counts.values()) == {wl["cycle"] // 3}
    assert [i["rows"] for i in a] == [i["rows"] for i in a2]
    assert [i["u"] for i in a] == [i["u"] for i in a2]
    assert [i["rows"] for i in a] != [i["rows"] for i in b]
    assert [i["index"] for i in a] == list(range(wl["cycle"]))


def test_uneven_shares_round_to_the_cycle():
    mix = {"cycle": 10, "mix": [{"share": 2, "item": {"k": 1}}, {"share": 1, "item": {"k": 2}}]}
    items = tf.cycle_items(mix)
    assert len(items) == 10 and sum(i["k"] == 1 for i in items) == 7
