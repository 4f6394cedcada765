"""Share of the traced window the device spent in the AdamW update, its
global norm and clip included (scope ``optimizer``; ``chipbench/scopes.py``)."""
from chipbench import scopes


def read(run):
    return scopes.share(run, {"optimizer"})
