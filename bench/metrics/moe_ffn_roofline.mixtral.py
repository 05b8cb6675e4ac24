"""moe_ffn_roofline.mixtral: Roofline share of the Pallas expert FFN kernel inside the decode program (device trace, expert counts). Read in the big-expert chat cell, where the kernel is bound by the expert-weight read."""
from readers import expert_ffn_roofline as read  # noqa: F401
