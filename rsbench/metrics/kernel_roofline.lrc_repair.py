"""Kernel roofline share (%) of the LRC repair cell: the traffic's least
GEMM work, 1 x 6 x chunk for each local repair and 1 x 12 x chunk for
each global one, against the device time of the GEMM executables in the
traced window."""

from rsbench.readers import kernel_roofline as read  # noqa: F401
