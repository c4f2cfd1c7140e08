"""latency_p50_ms: median latency of the requests due in the window, each
timed from its due time until its answer is ready on the device
(``block_until_ready``); an answer that never came counts with the time
waited for it."""
import numpy as np


def read(record: dict) -> float | None:
    lat = [r["done"] - r["due"] for r in record["requests"]
           if r["done"] is not None]
    return 1e3 * float(np.percentile(lat, 50)) if lat else None
