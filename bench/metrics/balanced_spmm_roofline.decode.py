"""The balanced-spmm kernels' share of their roofline in the window."""
from bench.metrics import spmm_roofline as read  # noqa: F401
