"""step_mfu.chat: Useful model FLOPs of the window's engine steps over their wall time and the chip's peak (host clock). Read in the chat cells."""
from readers import step_mfu as read  # noqa: F401
