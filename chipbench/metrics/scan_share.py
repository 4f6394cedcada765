"""Share of the traced window the device spent in the layer scan's own
slicing of stacked params and caches and stacking of outputs (scope
``layers/scan``, every phase; ``chipbench/scopes.py``)."""
from chipbench import scopes


def read(run):
    return scopes.share(run, {scopes.SCAN})
