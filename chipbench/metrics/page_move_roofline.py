"""Kernels: the cleaner's page move (segment_compact), pages moved in the
window read and written at HBM bandwidth over the kernel's time in the
trace, in %."""
from chipbench.metrics._roofline import page_move as read  # noqa: F401
