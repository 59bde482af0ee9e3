"""Host ms per engine step: `bench.step_all` wall time less the device
busy time inside it, averaged over the steps of the traced window."""
from readers import host_ms_per_step as read  # noqa: F401
