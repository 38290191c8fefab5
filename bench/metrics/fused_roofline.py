"""Kernels (kernels/threshold_ssum.py, the fused runner): bytes the
window's fused-planned queries need, over the device time of the fused
runner's programs times the chip's peak HBM bandwidth."""
from yardstick.roofline import hbm_share

#: program names of the fused runner in the device trace: the multi-query
#: circuit runner and the bare-threshold kernel
PROGRAMS = ("jit_run", "jit_threshold_pallas")


def read(rec):
    return hbm_share(rec, "fused", PROGRAMS)
