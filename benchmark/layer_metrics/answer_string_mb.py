"""UTF-8 bytes the STRING columns of a refresh's answers stand for: the
engines' ``usage.string_bytes_out`` (the ``string_bytes`` of their
``payload`` spans of kind ``result``; the answer crosses as ids and
their dictionaries, the client's decode hands out the strings by
reference). Summed over a refresh's requests, median over the window's
refreshes, in MB. Nothing on a program whose usage record has no such
counter."""

from .answer_rows import usage_counter


def read(ctx):
    out = usage_counter(ctx, "string_bytes_out")
    return None if out is None else out / 1e6
