"""Host milliseconds inside ``AnalyticsFrontend.step`` outside its child
spans (drain, per-request validation, grouping and scatter), per answered
request, outside the traced part of the window (``repro.obs`` ring:
``repro.frontend.step`` less its children)."""
import spans


def read(record: dict) -> float | None:
    return spans.self_ms_per_request(record)
