"""Kernels: paged decode attention's least time on the chip (live K/V
bytes at HBM bandwidth; it is memory-bound) over its summed kernel time in
the trace, in %."""
from chipbench.metrics._roofline import paged_attention as read  # noqa: F401
