"""Share of the traced window in which no program ran, averaged over the chips."""
from bench.metrics._idle import idle_pct as read  # noqa: F401
