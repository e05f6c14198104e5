"""Share of the window in which no operation ran on the device."""
from bench.metrics import idle_share as read  # noqa: F401
