"""What several per-layer readers share."""


def fixed_shape(config: dict) -> tuple:
    """(rows, width, storage dtype) of the fixed-effect design."""
    fixed = next(c for c in config["coordinates"] if c["kind"] == "fixed")
    return (int(config["users"]) * int(config["rows_per_user"]),
            int(fixed["dim"]), fixed.get("storage_dtype"))


def obs_spans_named(readings: dict, name: str) -> list:
    return [s for s in readings["obs_spans"] if s["name"] == name]
