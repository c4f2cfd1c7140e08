"""Host milliseconds inside ``AnalyticsFrontend.step`` per answered
request, outside the traced part of the window: the frontend and query
planner's host path."""
import reduce


def read(record: dict) -> float | None:
    return reduce.host_ms_per_request(record)
