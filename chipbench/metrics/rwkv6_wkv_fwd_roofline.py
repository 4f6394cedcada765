"""Roofline share of the ``rwkv6_wkv_fwd`` Pallas kernel (see ``roofline.py``)."""
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))
from roofline import share  # noqa: E402


def read(run):
    return share(run, "rwkv6_wkv_fwd")
