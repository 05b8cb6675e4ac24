"""moe_ffn_roofline.chat: Roofline share of the Pallas expert FFN kernel inside the decode program (device trace, expert counts). Read in the chat cells."""
from readers import expert_ffn_roofline as read  # noqa: F401
