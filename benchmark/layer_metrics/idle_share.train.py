"""Share of the traced window in which no operation ran on the device
(1 - union of the op line's busy intervals / window), on the WORST of the devices. Source: device_trace."""

from harness.readers import idle_share as read  # noqa: E402,F401
