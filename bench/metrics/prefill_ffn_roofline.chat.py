"""prefill_ffn_roofline.chat: Roofline share of the Pallas expert FFN kernel inside the prefill programs (device trace, prompt tokens). Read in the big-expert chat cell, where a prefill reads every expert's weights."""
from prefill_roofline import prefill_ffn_roofline as read  # noqa: F401
