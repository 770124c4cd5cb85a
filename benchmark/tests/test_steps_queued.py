"""The `steps_queued` reader's own cases (`steps_queued_pct.tok`);
`tests/test_llm_tick_spans.py` runs them in tier-1."""

from __future__ import annotations

import pytest

from benchmark.readers import steps_queued


def _stats(steps, queued=None):
    decode = {"path": "pallas", "steps": steps}
    if queued is not None:
        decode["steps_queued"] = queued
    return {"decode": decode}


@pytest.mark.parametrize("before, after, want", [
    # three steps of four left while the one before them was unread
    (_stats(200, 120), _stats(3200, 2370), 75.0),
    # an owner for whom somebody always waits: every step read first
    (_stats(200, 0), _stats(3200, 0), 0.0),
    # a program without the counter (the parent), one that loses it, no
    # stats at all, and a window in which no step ran: nothing, no raise
    (_stats(200), _stats(3200), None),
    (_stats(200, 120), _stats(3200), None),
    ({}, {}, None),
    (None, None, None),
    (_stats(200, 120), _stats(200, 120), None),
])
def test_steps_queued_share_on_hand_made_counters(before, after, want):
    ctx = {"stats_before": before, "stats_after": after}
    got = steps_queued.read(ctx, {})
    assert got == (pytest.approx(want) if want is not None else None)
    assert steps_queued.read({}, {}) is None
