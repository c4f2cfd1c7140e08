"""Engine jit-cache misses in the window: ``repro.engine.build`` spans
that start inside it (``repro.obs`` ring), whether or not a backend
compile follows."""
import spans


def read(record: dict) -> int | None:
    return spans.builds_in_window(record)
