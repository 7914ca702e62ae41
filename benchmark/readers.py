"""What the metric readers share: the client's clock per refresh and
per request, and the program's finished traces joined to requests by
query id. A reader is handed ``ctx`` (see ``harness.run_cell``)."""

from __future__ import annotations

import numpy as np


def refresh_ms(ctx) -> np.ndarray:
    """Each completed refresh of the window, first call to last rows."""
    return np.asarray([
        (recs[-1]["t1"] - recs[0]["t0"]) * 1e3
        for recs in ctx["window"]["refreshes"]
    ])


def request_ms(ctx, label: str | None = None) -> dict:
    """{qid: ms on the client's clock} of the window's requests."""
    return {
        r["qid"]: (r["t1"] - r["t0"]) * 1e3
        for recs in ctx["window"]["refreshes"] for r in recs
        if label is None or r["label"] == label
    }


def percentile(values, q: float):
    """Linear interpolation between order statistics; None when empty."""
    if len(values) == 0:
        return None
    return float(np.percentile(values, q))


def window_s(ctx) -> float:
    w = ctx["window"]
    return w["t_close"] - w["t_open"]


def engine_ms(ctx) -> dict:
    """{qid: ms of the PEM engine's query span}."""
    return {t.qid: t.duration_s * 1e3 for t in ctx["spans"]["pem"]
            if t.qid and t.kind == "fragment"}


def per_refresh(ctx, by_qid: dict) -> list:
    """A per-request quantity summed over each refresh's requests; a
    refresh with a request missing from ``by_qid`` is left out. (The
    two scripts differ, so a median per request would sit between two
    modes and flip from run to run; per refresh it is one mode.)"""
    return [sum(by_qid[r["qid"]] for r in recs)
            for recs in ctx["window"]["refreshes"]
            if all(r["qid"] in by_qid for r in recs)]


def span_ms(trace, name: str) -> float:
    return sum(
        (s.end_unix_nano - s.start_unix_nano) / 1e6
        for s in trace.spans if s.name == name and s.end_unix_nano
    )


def op_seconds(ctx, pattern) -> float | None:
    """Device seconds of the traced window in ops whose name matches."""
    if ctx["trace"] is None:
        return None
    return sum(v for k, v in ctx["trace"]["ops"].items() if pattern(k))
