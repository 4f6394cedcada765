"""Share of the traced window the device spent in attention's backward
pass (scope ``attn/core``, phase ``bwd``: the jnp backward of the kernel;
``chipbench/scopes.py``)."""
from chipbench import scopes


def read(run):
    return scopes.share(run, {"attn/core"}, phase="bwd")
