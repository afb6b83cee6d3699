"""Open-loop driving of the engine through its public API.

Requests are submitted with ``Engine.submit`` when they fall due on the
harness's clock, whatever the engine is doing. The engine is stepped
with ``Engine.run(max_ticks=eng.ticks + 1)``: one admission pass
(prefills of queued requests into free slots) and one batched decode
tick. Each output token is stamped with the clock reading at the step
boundary where it appeared, and every request is timed from when it was
due, so a stall also delays the requests that wait behind it. A request
is served ``out_len`` tokens: the engine has one output limit for all
requests, so the harness cancels each request once its own count is
reached (the engine then delivers what it produced). When nothing is
queued or in flight the harness sleeps until the next request is due.

Host spans (``jax.profiler.TraceAnnotation``) mark what the harness is
doing, so that a traced run can attribute each idle gap of the device.
"""
from __future__ import annotations

import dataclasses
import time

import numpy as np

#: outcomes of a request that was served in full
SERVED = ("ok", "done")


@dataclasses.dataclass
class Record:
    due: float                 # clock reading when the request fell due
    prompt: tuple
    out_len: int
    rid: int = -1
    late: float = 0.0          # submit time - due time
    tokens: list = dataclasses.field(default_factory=list)
    times: list = dataclasses.field(default_factory=list)
    outcome: str | None = None  # engine outcome, or "done" when cut by us


def _collect(eng, inflight: set, by_rid: dict, t: float) -> None:
    live = {s.request_id: s.generated for s in eng.slots if s.active}
    for rid in list(inflight):
        rec = by_rid[rid]
        toks = live.get(rid)
        if toks is None:
            outcome = eng.outcome(rid)
            if outcome is None:
                continue  # still queued
            toks = eng.outputs.get(rid, [])
            rec.outcome = outcome
            inflight.discard(rid)
        have = min(len(toks), rec.out_len)
        if have > len(rec.tokens):
            rec.times.extend([t] * (have - len(rec.tokens)))
            rec.tokens.extend(toks[len(rec.tokens):have])
        if rec.outcome is None and have >= rec.out_len:
            eng.cancel(rid)
            rec.outcome = "done"
            inflight.discard(rid)


def drive(eng, reqs, *, start: float, open_t: float, close_t: float,
          grace_s: float, annotate, on_open, on_close,
          clock=time.perf_counter) -> list:
    """Run the schedule ``reqs`` (``generator.Request``) from clock
    reading ``start`` until every request due in ``[open_t, close_t)``
    has its first token (or failed), or ``close_t + grace_s`` passed.
    ``on_open``/``on_close`` are called at the first step boundary at or
    after ``open_t``/``close_t``."""
    recs = [Record(start + r.due, r.prompt, r.out_len) for r in reqs]
    due_in = [r for r in recs if open_t <= r.due < close_t]
    by_rid: dict = {}
    inflight: set = set()
    marks = [(open_t, on_open), (close_t, on_close)]
    nxt = 0
    while True:
        now = clock()
        while marks and now >= marks[0][0]:
            marks.pop(0)[1]()
        while nxt < len(recs) and recs[nxt].due <= now:
            rec = recs[nxt]
            with annotate("bench.submit"):
                rec.rid = eng.submit(list(rec.prompt))
            rec.late = clock() - rec.due
            by_rid[rec.rid] = rec
            rec.outcome = eng.outcome(rec.rid)
            if rec.outcome is None:
                inflight.add(rec.rid)
            nxt += 1
        if now >= close_t and all(r.times or r.outcome for r in due_in):
            break
        if now > close_t + grace_s:
            break
        if inflight:
            with annotate("bench.step"):
                eng.run(max_ticks=eng.ticks + 1)
            t = clock()
            with annotate("bench.collect"):
                _collect(eng, inflight, by_rid, t)
        elif nxt < len(recs):
            wake = min([recs[nxt].due] + [t for t, _ in marks[:1]])
            with annotate("bench.idle"):
                time.sleep(max(0.0, wake - clock()))
        else:
            break
    for _, fn in marks:  # a schedule that ran dry before the window closed
        fn()
    return recs


def end_to_end(recs: list, open_t: float, close_t: float,
               stop_t: float) -> dict:
    """The window's end-to-end numbers (seconds), from token stamps."""
    due_in = [r for r in recs if open_t <= r.due < close_t]
    failed = [r for r in due_in
              if not r.times or (r.outcome is not None
                                 and r.outcome not in SERVED)]
    # a request that never got its first token waited at least until the
    # run stopped: it misses every limit
    ttft = [(r.times[0] if r.times else stop_t) - r.due for r in due_in]
    stamps = [t for r in recs for t in r.times if open_t <= t < close_t]
    gaps = [b - a for r in recs for a, b in zip(r.times, r.times[1:])
            if open_t <= a and b < close_t]
    late = [r.late for r in recs if r.rid >= 0]
    return {
        "attempted": len(due_in), "failed": len(failed),
        "output_tokens": len(stamps),
        "output_tokens_per_s": len(stamps) / (close_t - open_t),
        "ttft_p95_s": float(np.percentile(ttft, 95)) if ttft else None,
        "itl_p50_s": float(np.percentile(gaps, 50)) if gaps else None,
        "itl_p95_s": float(np.percentile(gaps, 95)) if gaps else None,
        "gaps": len(gaps),
        "late_p50_s": float(np.percentile(late, 50)) if late else 0.0,
        "late_max_s": float(max(late)) if late else 0.0,
    }
