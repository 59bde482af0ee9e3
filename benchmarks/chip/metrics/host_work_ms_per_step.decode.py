"""Mean device-idle ms inside `engine.step` and outside its
`engine.readback`, per plain decode step: the host work of a step that
overlap with the device would hide."""
from program_spans import host_work_ms_per_step as read  # noqa: F401
