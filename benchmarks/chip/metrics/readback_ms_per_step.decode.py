"""Mean device-idle ms inside the engine's `engine.readback` span (the
sampled tokens read back to the host) per plain decode step."""
from program_spans import readback_ms_per_step as read  # noqa: F401
