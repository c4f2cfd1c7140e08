"""Share of the HBM roofline of the 13-variable statistics + laplacian
work: the bytes the requests answered in the traced window need
(``bytes.py``), at the chip's peak bandwidth, over the device's busy time
in that window."""
import reduce


def read(record: dict) -> float | None:
    return reduce.roofline_share(record, lambda r: True)
