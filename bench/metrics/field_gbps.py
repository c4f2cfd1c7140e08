"""field_gbps: GB of original float32 field covered by the requests
answered inside the window (each distinct field of a request once, over
its region or the whole field), per second of the window."""


def read(record: dict) -> float | None:
    _, end = record["window"]
    covered = sum(r["covered"] for r in record["requests"]
                  if r["error"] is None and r["done"] is not None
                  and r["done"] <= end)
    return covered / record["seconds"] / 1e9 if covered else None
