"""Whole-step share of the bf16 peak in the mlp cells (``reduce.train_mfu``)."""
from reduce import train_mfu as read  # noqa: F401
