"""Pieces every driver uses: host spans, device memory, and comparisons."""
from __future__ import annotations

import collections
import contextlib
import time

import jax
import jax.numpy as jnp
import numpy as np


class Spans:
    """Host spans: each is written into the profiler's trace as
    ``bench:<name>`` and its host-clock seconds are summed here."""

    def __init__(self):
        self.seconds = collections.Counter()

    @contextlib.contextmanager
    def __call__(self, name):
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(f"bench:{name}"):
            yield
        self.seconds[name] += time.perf_counter() - t0


def program_bytes(compiled):
    """Device bytes one compiled program holds while it runs: arguments,
    outputs and temporaries, less the outputs that alias donated inputs."""
    try:
        m = compiled.memory_analysis()
    except (NotImplementedError, RuntimeError):  # a backend without the analysis
        return 0
    if m is None:
        return 0
    return int(m.argument_size_in_bytes + m.output_size_in_bytes
               + m.temp_size_in_bytes - m.alias_size_in_bytes)


def peak_bytes(device):
    return int((device.memory_stats() or {}).get("peak_bytes_in_use", 0))


def rel_l2(x, ref):
    x, ref = np.asarray(x, np.float64), np.asarray(ref, np.float64)
    return float(np.linalg.norm(x - ref) / np.linalg.norm(ref))


def token_gaps(ref_logits, tokens):
    """How far below the reference's best logit each served token's logit
    lies, in the reference's logits.  ref_logits (N, V); tokens (N,)."""
    ref = np.asarray(ref_logits, np.float64)
    picked = np.take_along_axis(ref, np.asarray(tokens)[:, None], axis=1)[:, 0]
    return ref.max(axis=1) - picked


def leaf_norms(tree):
    """Per-leaf float32 L2 norms, as a flat list in tree order."""
    fn = jax.jit(lambda t: [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                            for x in jax.tree.leaves(t)])
    return [float(x) for x in fn(tree)]


def worst_leaf_gap(prog, ref, keep=None):
    """max over leaves of |prog - ref| / max(ref, median ref): a gap of
    norms, each against the leaf's own reference norm or the median leaf's,
    whichever is larger (some gradients are all but zero)."""
    prog, ref = np.asarray(prog, np.float64), np.asarray(ref, np.float64)
    keep = np.ones(len(ref), bool) if keep is None else np.asarray(keep)
    med = float(np.median(ref[keep]))
    return float(np.max(np.abs(prog - ref)[keep] / np.maximum(ref[keep], med)))
