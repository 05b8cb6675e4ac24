"""prefill_ms_per_ktok.chat: Device time of the batch-1 prefill programs per thousand prompt tokens (device trace). Read in the chat cells, where a step that prefills holds back every decoding request."""
from readers import prefill_ms_per_ktok as read  # noqa: F401
