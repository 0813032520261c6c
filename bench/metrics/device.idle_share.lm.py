"""Device idle share of the lm cells (``reduce.idle_share``)."""
from reduce import idle_share as read  # noqa: F401
