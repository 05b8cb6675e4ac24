"""device_idle_share.chat: Idle share of the chip over the traced slice (device trace). Read in the chat cells."""
from readers import device_idle_share as read  # noqa: F401
