"""setup_s: seconds from process start to the first timed request: the
fields made and compressed, the store filled, every program compiled or
loaded from the cache, and the warm-up."""


def read(record: dict) -> float:
    return record["setup_s"]
