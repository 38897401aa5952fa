"""Peak bytes in use on the fullest chip over the chip's memory."""
from benchmark.reduce.peaks import hbm_share as read  # noqa: F401
