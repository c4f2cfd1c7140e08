"""Programs built inside the window (and its drain): backend compiles and
persistent-cache loads, counted by a ``jax.monitoring`` listener."""


def read(record: dict) -> int:
    return record["compiles_in_window"]
