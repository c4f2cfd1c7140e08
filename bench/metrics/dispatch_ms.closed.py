"""Host milliseconds in the engine on a jit-cache hit
(``repro.engine.dispatch``: the program's cache key and the jitted call)
per answered request, outside the traced part of the window
(``repro.obs`` ring)."""
import spans


def read(record: dict) -> float | None:
    return spans.child_ms_per_request(record, spans.DISPATCH)
