"""Share of the traced window the device spent in attention and its cache
update (scopes ``attn/core`` and ``attn/cache_write``, every phase;
``chipbench/scopes.py``)."""
from chipbench import scopes


def read(run):
    return scopes.share(run, {"attn/core", "attn/cache_write"})
