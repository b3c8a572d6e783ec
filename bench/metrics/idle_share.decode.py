"""Device idle share of the decode cells' traced window."""
from harness.measure import idle_share as read  # noqa: F401
