"""Backend compiles and persistent-cache loads that JAX reported inside the
window, plus misses of fusion's executable cache; should be 0."""


def read(run):
    c = run["counters"]
    return float(c["jax_compiles"] + c["fusion_misses"])
