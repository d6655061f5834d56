# repro.core's sweep driver imports these kernels; loading the core
# package first lets either package be imported first.
import repro.core  # noqa: F401  (import order, see above)

from . import megakernel, ops
from .ops import epoch_schedule, epoch_schedule_compact
