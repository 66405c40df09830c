"""Kernel roofline share (%) of the store cells: the least GEMM work of
the window's traffic (a p x k parity delta per acknowledged PUT, a 1 x k
rebuild per distinct record read from a stripe with a lost chunk)
against the device time of the GEMM executables in the traced window."""

from rsbench.readers import kernel_roofline as read  # noqa: F401
