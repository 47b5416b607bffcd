"""Host time per request in KV staging: the prefiller's ``stage_cache``
and the decoder's cache reassembly and upload (``_assemble_cache``)."""
from harness.readers import per_request_ms


def read(data):
    return per_request_ms(data, "kv.stage", "kv.fill")
