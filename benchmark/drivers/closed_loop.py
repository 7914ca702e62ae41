"""Closed loop, ``clients`` = 1: a refresh is the traffic's scripts in
order, back to back; the next refresh starts when the last script's
decoded rows are in hand (plus ``think_ms``). Upstream's exectime
harness runs its scripts this way.

A refresh starts only while the window is open; the window closes when
the refresh that crosses ``seconds`` completes, so every request that
was started is timed and counted, over all the time it took.
"""

from __future__ import annotations

import time


def refresh(stack, requests, now_ns, timeout_s, mark):
    """One refresh: (per-request records, the scripts' rows)."""
    recs, answers = [], []
    for req in requests:
        with mark(f"request:{req['label']}"):
            t0 = time.perf_counter()
            res = stack.execute(req["pxl"], timeout_s, now_ns)
            t1 = time.perf_counter()
        recs.append({"label": req["label"], "t0": t0, "t1": t1,
                     "qid": res["qid"], "partial": res["partial"]})
        answers.append(res["rows"])
    return recs, answers


def run(stack, traffic, requests, seconds, now_ns, mark) -> dict:
    if traffic["clients"] != 1:
        raise ValueError("closed_loop drives one client")
    think_s = traffic["think_ms"] / 1e3
    out = {"refreshes": [], "answers": [], "attempted": 0, "failed": 0,
           "errors": [], "cpu_s": 0.0}
    cpu0 = time.process_time()
    t_open = time.perf_counter()
    out["t_open"] = t_open
    while time.perf_counter() - t_open < seconds:
        out["attempted"] += 1
        try:
            recs, answers = refresh(
                stack, requests, now_ns, traffic["timeout_s"], mark
            )
        except Exception as e:  # a failed refresh is counted, not fatal
            out["failed"] += 1
            if len(out["errors"]) < 5:
                out["errors"].append(f"{type(e).__name__}: {e}"[:300])
            continue
        with mark("check"):
            # Kept for the comparison once the window has closed.
            out["refreshes"].append(recs)
            out["answers"].append(answers)
        if think_s:
            with mark("think"):
                time.sleep(think_s)
    out["t_close"] = time.perf_counter()
    out["cpu_s"] = time.process_time() - cpu0
    return out
