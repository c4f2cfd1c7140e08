"""latency_p95_ms: 95th percentile of the latencies of ``latency_p50_ms``,
over the same requests."""
import numpy as np


def read(record: dict) -> float | None:
    lat = [r["done"] - r["due"] for r in record["requests"]
           if r["done"] is not None]
    return 1e3 * float(np.percentile(lat, 95)) if lat else None
