"""Share of the traced window that the chips spent in collective
operations, averaged over the chips: the device time of every
all-gather, reduce-scatter, all-reduce, all-to-all and
collective-permute, of their ``-start`` and ``-done`` halves, and of the
TPU's asynchronous collective fusions (``async-collective-start`` /
``-done``).  For an asynchronous collective this is the part of it that
the compute around it did not hide."""
import re

KINDS = r"(all-gather|reduce-scatter|all-reduce|all-to-all|collective-permute)"
OPCODE = re.compile(KINDS + r"(-start|-done)?$")
NAME = re.compile(r"(async-collective|" + KINDS + r")(-start|-done)?([.-]|$)")


def is_collective(label: str) -> bool:
    """``label`` is an op of the reduced trace, ``name opcode shape``."""
    parts = label.split(" ")
    return bool(NAME.match(parts[0])
                or (len(parts) > 1 and OPCODE.match(parts[1])))


def read(ctx):
    if ctx.trace is None or ctx.trace["window_s"] <= 0:
        return None
    secs = sum(t for n, t in ctx.trace["ops"].items() if is_collective(n))
    return 100.0 * secs / ctx.trace["window_s"]
