"""Device time per call of the decode-step program, from the trace."""
from harness.readers import program_ms


def read(data):
    return program_ms(data, r"decode_step")
