"""decode_step_ms.mixtral: Device time of one run of the whole-model decode program (device trace). Read in the big-expert chat cell, where the step reads every expert's weights."""
from readers import decode_step_ms as read  # noqa: F401
