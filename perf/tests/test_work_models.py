"""Work models: hand-checked FLOPs and bytes, and a share that cannot pass
100% for any device time at or above the floor."""

import pytest

from perf import manifest as mf
from perf.work_models import floor_seconds

PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def cells():
    manifest = mf.load_manifest()
    return [mf.load_cell(manifest, c["name"], False) for c in manifest["workloads"]]


def test_peaks_table():
    peaks = mf.load_peaks("TPU v5 lite")
    assert peaks["bf16_flops_per_s"] == 197e12 and peaks["hbm_bytes_per_s"] == 819e9
    with pytest.raises(mf.ManifestError):
        mf.load_peaks("TPU v9 imaginary")


def test_hand_values():
    by_name = {c["cell"]["name"]: c for c in cells()}
    fit = by_name["kmeans_fit"]
    w = mf.load_module("work_models", "lloyd_fit").work(fit["config"], {}, 1)
    rows = 2e7 * 64
    assert w["bytes"] == pytest.approx(31 * rows * 4)                # 158.7 GB: the stored width
    assert w["flops"] == pytest.approx((31 + 30) * 2 * rows * 8)
    assert floor_seconds(w, PEAKS) == (pytest.approx(w["bytes"] / 819e9), "hbm")
    qr = by_name["qr_tall"]
    w = mf.load_module("work_models", "qr_reduced").work(qr["config"], {}, 1)
    assert w["flops"] == pytest.approx(2e12 - 4e9 / 3)
    assert floor_seconds(w, PEAKS) == (pytest.approx(w["flops"] / 197e12), "flops")
    w4 = mf.load_module("work_models", "qr_reduced").work(dict(qr["config"], rows=2e6), {}, 4)
    assert w4["flops"] == pytest.approx(w["flops"])                  # rows divide over the chips


@pytest.mark.parametrize("factor", [1.0, 1.0001, 3.0, 1e4])
def test_share_never_passes_100(factor):
    from perf.layer_metrics import kernel_roofline

    for cell in cells():
        model = mf.load_module("work_models", cell["workload"]["work_model"])
        for entry in cell["workload"]["traffic"]["mix"]:
            work = model.work(cell["config"], entry["item"], cell["cell"]["chips"])
            floor, _ = floor_seconds(work, PEAKS)
            assert floor > 0
            run = {"trace": {"devices": [{"busy_s": floor * factor}], "fullest": 0},
                   "floor_s": floor}
            share = kernel_roofline.read(run)
            assert 0 < share <= 100.0 + 1e-9
