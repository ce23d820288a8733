"""Device time in collective operations, from a traced window's reduced
trace (``bench/trace.py``): the ops whose HLO names are collectives (a
collective-permute's start and done, all-reduce, all-gather, ...), summed
inside the benchmark's spans of one name, averaged over devices, over the
steps or sweeps those spans hold."""
from __future__ import annotations

import re

from bench import trace as T

#: HLO instruction names of collectives, as the profiler names their ops
COLLECTIVE_OP = re.compile(
    r"^%?(collective-permute|all-reduce|all-gather|reduce-scatter|all-to-all"
    r"|ppermute|psum|psum_invariant|pmax|pmin|all_gather|all_to_all"
    r"|reduce_scatter)"
    r"(-start|-done)?(\.\d+)?$")


def is_collective(op: str) -> bool:
    return bool(COLLECTIVE_OP.match(op))


def collective_s(tr: dict, name: str) -> float | None:
    """Seconds a unit (step or sweep) of the spans ``name`` spends in
    collective ops, mean over devices; None without spans or devices."""
    win = T.spans(tr, name)
    units = sum(w[3] for w in win)
    if not win or not units or not tr["devices"]:
        return None
    per_dev = [T.busy_ns([o for o in ops if is_collective(o[0])], win)
               for ops in tr["devices"].values()]
    return sum(per_dev) / len(per_dev) * 1e-9 / units
