"""Roofline share of the ``flash_attention_fwd`` Pallas kernel (see ``roofline.py``)."""
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))
from roofline import share  # noqa: E402


def read(run):
    return share(run, "flash_attention_fwd")
