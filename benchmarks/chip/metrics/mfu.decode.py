"""Model FLOP utilization of the decode window: model FLOPs of the
tokens served per second, over the chip's bf16 peak."""
from readers import step_mfu as read  # noqa: F401
