"""The yardstick's own rules, as cases (CPU, no runtime):

- a routed family cannot be held to the plain check, can be held to a
  check it owns, and that check still refuses the precision below;
- the family-owned path leaves a dense family's report as it was;
- `selftest.shrink` with and without `family.TINY`;
- the seed rule of every traffic file.
"""

from __future__ import annotations

import os

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")

from benchmark import selftest, traffic                    # noqa: E402
from benchmark.tests import toy_routed                     # noqa: E402

SEEDS = list(selftest.ROUTED_SEEDS)


@pytest.mark.parametrize("seed", SEEDS)
def test_owned_check_passes_where_the_plain_one_may_not(seed):
    r = selftest.routed_report(seed)
    assert r["ok"] and r["owned_by"].endswith("toy_routed"), r
    assert r["forgiven"]["outside_zone"] == 0
    for key in ("prefill_logit_max", "prefill_logit_rms", "margins"):
        assert key in r["plain"]            # kept under either path


def test_plain_check_fails_a_routed_family_on_some_seeds():
    fails = [s for s in SEEDS if not selftest.routed_report(s)["plain"]["ok"]]
    assert 0 < len(fails) < len(SEEDS), fails


@pytest.mark.parametrize("seed", SEEDS)
def test_owned_check_refuses_fp8_weights(seed):
    r = selftest.routed_report(seed, weights="float8_e4m3fn")
    assert not r["ok"], r


@pytest.mark.parametrize("seed", SEEDS[:3])
def test_owned_check_refuses_a_decision_outside_the_zone(seed):
    r = selftest.routed_report(seed, flip=2)
    assert not r["ok"] and r["forgiven"]["outside_zone"] > 0, r


def test_an_owned_check_must_report_what_it_forgave():
    family = type("silent", (), dict(
        TOLERANCE=toy_routed.TOLERANCE,
        reference_logits=toy_routed.reference_logits,
        check=lambda *a: {"ok": True, "tolerance": {}}))
    with pytest.raises(KeyError):
        selftest.routed_report(SEEDS[0], family=family)


def test_dense_report_is_what_the_parent_wrote():
    selftest.check_dense_report()


@pytest.mark.parametrize("tiny", [None, toy_routed.TINY])
def test_shrink_applies_the_familys_tiny_after_its_own(tiny):
    selftest.check_shrink(tiny)


@pytest.mark.parametrize("name", sorted(
    n[:-5] for n in os.listdir(os.path.join(traffic.HERE, "traffic"))))
def test_seed_rule(name):
    selftest.check_traffic_file(name)
