"""One exact Lloyd fit: every iteration must read the rows once (the new
centres are not known before the last row of the pass before), and the labels
the fit returns need one more pass.  After benchmarks/cb's one-pass byte
model.  A pass reads the rows in the width they are stored in, whatever the
width the program multiplies in: the floor follows the configuration, never
what ran.  FLOPs: the cross term of every pass and the masked sums of every
iteration, 2*rows*features*k each.
"""


def work(config: dict, item: dict, chips: int) -> dict:
    fit = config["fit"]
    rows, feats = config["rows"] / chips, config["features"]
    passes = fit["max_iter"] + 1
    stored = 4 if config["dtype"] == "float32" else 2
    return {
        "flops": passes * 2.0 * rows * feats * fit["n_clusters"]
        + fit["max_iter"] * 2.0 * rows * feats * fit["n_clusters"],
        "bytes": passes * rows * feats * stored,
    }
