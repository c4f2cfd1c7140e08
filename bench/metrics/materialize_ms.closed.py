"""Host milliseconds in store materializations (``repro.store.materialize``:
a miss's decode and recorrelation, dispatched op by op) per answered
request, outside the traced part of the window (``repro.obs`` ring)."""
import misses


def read(record: dict) -> float | None:
    r = misses.in_steps(record)
    if r is None:
        return None
    found, answered = r
    return sum(e - s for _, s, e, _, _ in found) * 1e-6 / answered
