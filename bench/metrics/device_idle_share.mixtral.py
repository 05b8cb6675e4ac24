"""device_idle_share.mixtral: Idle share of the chip over the traced slice (device trace). Read in the big-expert chat cell, where the device and not the host should set the pace."""
from readers import device_idle_share as read  # noqa: F401
