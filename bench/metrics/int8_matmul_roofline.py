"""``kernels/int8_matmul.py``'s share of its roofline (see
``harness/roofline.py``)."""
from harness import roofline


def read(run):
    return roofline.share(run, "int8_matmul")
