"""Share of the HBM roofline of the 2-D decode+stencil work: the bytes
the stencil requests answered in the traced window need (``bytes.py``:
the stage input plane read once, each output plane written once), at the
chip's peak bandwidth, over the device's busy time in that window."""
import reduce


def read(record: dict) -> float | None:
    return reduce.roofline_share(record, lambda r: r["stencil"])
