"""Per cent of the traced window in which the device ran nothing while the
host was inside ``repro.frontend.step``, the device's times shifted onto
the host's clock (``spans.alignment``)."""
import spans


def read(record: dict) -> float | None:
    return spans.idle_in_step_share(record)
