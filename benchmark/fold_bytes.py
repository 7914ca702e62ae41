"""Bytes a window fold has to read, from shapes: the rows in the
request's time range times the widths of the columns its script reads
(``reads`` in the traffic file, widths in the configuration). What the
program reads beyond that (padding to whole windows, sort scratch) is
its cost, not the algorithm's need."""

from __future__ import annotations


def fold_bytes(config: dict, request: dict, rows_in_range: int) -> int:
    widths = config["columns"]
    return rows_in_range * sum(widths[c] for c in request["reads"])
