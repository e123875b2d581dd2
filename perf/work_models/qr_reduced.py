"""A reduced QR with explicit Q of an m x n matrix, rows divided over the
chips.  Copied from benchmarks/cb/config.py ``qr_flops``: Householder R
(2mn^2 - 2n^3/3) plus forming Q (2mn^2 - 2n^3/3).  Bytes: read A, write Q
and R once."""


def work(config: dict, item: dict, chips: int) -> dict:
    m, n = config["rows"] / chips, config["cols"]
    itemsize = 4 if config["dtype"] == "float32" else 2
    return {
        "flops": 4.0 * m * n * n - (4.0 / 3.0) * n ** 3,
        "bytes": (2.0 * m * n + n * n) * itemsize,
    }
