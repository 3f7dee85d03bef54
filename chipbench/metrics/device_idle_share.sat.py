"""Device: 1 - (union of the device's op intervals) / traced window, in %
(profiler trace)."""
from chipbench.metrics._idle import idle as read  # noqa: F401
