"""Required FLOPs per second of the window over the chip's bf16 peak."""
from bench.metrics import mfu as read  # noqa: F401
