"""Host milliseconds in the store's ``seed`` calls (``repro.store.seed``;
a miss materializes inside it) per answered request, outside the traced
part of the window (``repro.obs`` ring)."""
import spans


def read(record: dict) -> float | None:
    return spans.child_ms_per_request(record, spans.SEED)
