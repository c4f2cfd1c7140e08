"""Per cent of the traced window in which the device ran no operation:
1 - (union of device-operation intervals) / window."""
import reduce


def read(record: dict) -> float | None:
    return reduce.idle_share(record)
