"""decode_step_ms.chat: Device time of one run of the whole-model decode program (device trace). Read in the chat cells."""
from readers import decode_step_ms as read  # noqa: F401
