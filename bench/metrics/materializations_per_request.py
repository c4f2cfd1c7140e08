"""Store materializations (``repro.store.materialize`` spans: cache misses
that built a stage) per answered request, outside the traced part of the
window (``repro.obs`` ring)."""
import misses


def read(record: dict) -> float | None:
    r = misses.in_steps(record)
    if r is None:
        return None
    found, answered = r
    return len(found) / answered
