"""Work models: the mandatory FLOPs and bytes of ONE call, from the
configuration's shapes alone.  One file per model, named by a workload's
``work_model``; each has ``work(config, item, chips) -> {"flops", "bytes"}``
per chip.  Never from the names of the ops that ran: the model reads the same
work whatever implements it, so a share of the roofline cannot pass 100%.
"""


def floor_seconds(work: dict, peaks: dict) -> tuple:
    """Least seconds one chip could take, and which bound sets it."""
    by_flops = work["flops"] / peaks["bf16_flops_per_s"]
    by_bytes = work["bytes"] / peaks["hbm_bytes_per_s"]
    return (by_flops, "flops") if by_flops >= by_bytes else (by_bytes, "hbm")
