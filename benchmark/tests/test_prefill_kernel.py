"""The `prefill_kernel` reader's own case (`prefill_kernel_pct.mla`), beside
`test_deepseek_v3.py`'s readers' test; `tests/test_moonlight_model.py` runs
it in tier-1."""

from __future__ import annotations

import pytest

from benchmark.readers import prefill_kernel


def test_prefill_kernel_share_on_hand_made_counters():
    """Whole prompts through the kernel over all the window's prefills; a
    program whose whole prompts build their scores (the parent) reads 0, one
    without the counter nothing, and no raise."""
    pre = lambda k, x: {"prefill": {"path": "xla", "kernel_calls": k,
                                    "xla_calls": x}}
    ctx = {"stats_before": pre(3, 10), "stats_after": pre(93, 290)}
    assert prefill_kernel.read(ctx, {}) == pytest.approx(100 * 90 / 370)
    ctx = {"stats_before": pre(0, 13), "stats_after": pre(0, 383)}
    assert prefill_kernel.read(ctx, {}) == 0.0
    for ctx in ({}, {"stats_before": {}, "stats_after": {}},
                {"stats_before": pre(0, 5), "stats_after": {"prefill": {}}},
                {"stats_before": pre(2, 5), "stats_after": pre(2, 5)}):
        assert prefill_kernel.read(ctx, {}) is None
