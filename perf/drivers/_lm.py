"""What the language-model generator and driver share: the configuration
file's published keys and assumed sizes as the program's config object, and as
the plain dictionary the reference reads."""

from perf.reference import sambay as ref


def sizes(config: dict) -> dict:
    """Published keys and assumed sizes in one flat dictionary."""
    out = {k: v for k, v in config.items() if isinstance(v, (int, float)) and not isinstance(v, bool)}
    out.update(config["assumed"]["sizes"])
    return out


def model_config(config: dict):
    from heat_tpu.models.sambay import SambaYConfig

    return SambaYConfig.from_dict(sizes(config), dtype=config["dtype"])


def reference_config(config: dict) -> dict:
    flat = sizes(config)
    return {k: flat[k] for k in ref.SIZES + ("num_hidden_layers", "mb_per_layer")}
