"""Model step: operations of the tokens prefilled and decoded in the
window over (device busy time of the traced window x peak bf16 FLOP/s),
in %."""
from chipbench.metrics._roofline import mfu as read  # noqa: F401
