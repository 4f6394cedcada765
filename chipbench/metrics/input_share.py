"""Share of the window the host spent making and sending batches: the
benchmark's own ``data`` and ``transfer`` spans around the program's
pipeline (``SyntheticLMDataset.batch``) and ``jnp.asarray``."""


def read(run):
    spans = run.get("spans_s") or {}
    if "data" not in spans or not run.get("elapsed_s"):
        return None
    return 100.0 * (spans["data"] + spans.get("transfer", 0.0)) / run["elapsed_s"]
