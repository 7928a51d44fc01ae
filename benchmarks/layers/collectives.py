"""The device trace's collective operations, for the readers of a cell on more
than one chip: all-reduce, all-gather, reduce-scatter, collective-permute and
all-to-all, plain or as an asynchronous `-start` / `-done` pair."""

import re

NAME = re.compile(r"^(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all)(-start|-done)?(\.\d+)?$")


def names(trace):
    """The traced collective operations' short names."""
    return [name for name in trace["op_self_s"] if NAME.match(name)]
