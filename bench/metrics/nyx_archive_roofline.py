"""Share of the HBM roofline of the NYX archive work (velocity divergence
and curl, density and temperature mean and std): the bytes the requests
answered in the traced window need (``bytes.py``: each field's stage
input plane read once, each output written once), at the chip's peak
bandwidth, over the device's busy time in that window.  The store's
decode and recorrelation are not in the bytes: they are what the cache
below the working set adds to the work."""
import reduce


def read(record: dict) -> float | None:
    return reduce.roofline_share(record, lambda r: True)
