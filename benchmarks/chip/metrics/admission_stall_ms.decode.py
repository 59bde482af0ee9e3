"""Per admitted request, ms of the steps that carried an admission beyond
a plain step's wall, plus the admissions made outside any step."""
from program_spans import admission_stall_ms as read  # noqa: F401
