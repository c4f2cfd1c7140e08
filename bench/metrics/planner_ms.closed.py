"""Host milliseconds in query planning (``repro.query.plan``: DAG
analysis, leaf resolution, bound validation, store residency probes, stage
planning) per answered request, outside the traced part of the window
(``repro.obs`` ring)."""
import spans


def read(record: dict) -> float | None:
    return spans.child_ms_per_request(record, spans.PLAN)
