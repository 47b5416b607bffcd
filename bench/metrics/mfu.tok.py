"""Required model FLOPs of the prefills and decode steps run in the window
(causal attention, routed top-k experts only) over window x chips x peak."""
from harness.readers import serving_mfu


def read(data):
    return serving_mfu(data)
