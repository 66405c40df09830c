"""Device idle share (%) of the LRC repair cell: 1 - busy / the traced
window, busy being the union of the device's op intervals."""

from rsbench.readers import device_idle as read  # noqa: F401
