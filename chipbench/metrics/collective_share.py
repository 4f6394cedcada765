"""Share of the chips' time spent in collective operations: the device self
time of every ``all-reduce``, ``all-gather``, ``reduce-scatter``,
``collective-permute`` and ``all-to-all`` (their ``-start``/``-done`` halves
and fusions named after them included), summed over the chips, over the
traced window times the chips (``chipbench/trace_reduce.py``)."""
import re

COLLECTIVE = re.compile(
    r"(?:^|[^a-z])(?:all-reduce|all-gather|reduce-scatter|collective-permute"
    r"|all-to-all)(?:$|[^a-z])")


def is_collective(op_name):
    return bool(COLLECTIVE.search(op_name.lower().replace("_", "-")))


def read(run):
    t = run.get("trace")
    if t is None or t.window_s <= 0 or t.devices == 0:
        return None
    own = sum(s for name, s in t.op_s.items() if is_collective(name))
    return 100.0 * own / (t.window_s * t.devices)
