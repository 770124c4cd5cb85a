"""One general traffic generator, driven by the parameter files under
`benchmark/traffic/`.

The rule every kind keeps: a traffic file fixes the MULTISET of requests
(prompt length, output length, document identity) and their SCHEDULE;
`--seed` draws the token ids and permutes which request takes which place
inside its stratum.  Two runs of a cell therefore offer the same load, and
their numbers differ only by what the system does.  `layout_seed` (in the
file) seeds everything structural; `--seed` never reaches it.

Kinds:
  closed       N callers over one fixed list of requests; each caller takes
               the list's next request when its last answer ends.  The
               callers start `stagger_tokens` tokens of the one before
               apart (serve_cell._closed_loop).
  open_grid    one request per slot of 1/rate seconds, at an offset inside
               the slot drawn from the seed: an open loop whose load per
               stratum is the same in every run.
  train_steps  optimizer steps on token batches made on the device.
"""

from __future__ import annotations

import dataclasses
import json
import math
import os
from statistics import NormalDist
from typing import Any, Dict, List, Optional

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def load(name: str) -> Dict[str, Any]:
    path = os.path.join(HERE, "traffic", f"{name}.json")
    with open(path) as f:
        spec = json.load(f)
    if spec.get("kind") not in ("closed", "open_grid", "train_steps"):
        raise ValueError(f"{path}: unknown kind {spec.get('kind')!r}")
    return spec


@dataclasses.dataclass
class Request:
    index: int                  # place in the cell's fixed list
    phase: str                  # "ramp", "window", "tail" (open_grid); "list"
    due: Optional[float]        # seconds from the start of the ramp
    prompt_len: int
    output_len: int
    doc: int = -1               # document identity, -1 = none shared
    doc_len: int = 0
    tokens: Optional[List[int]] = None      # filled by fill_tokens()

    def shape(self):
        """What the load depends on — and the seed may not change."""
        return (self.phase, self.prompt_len, self.output_len, self.doc_len)


# ------------------------------------------------------------ helpers -----

def _quantile_midpoints(dist: Dict[str, Any], n: int) -> List[int]:
    """n values at the quantile midpoints (i + 0.5) / n of a log-normal
    truncated to [min, max]: the same multiset whenever n is the same."""
    if dist.get("dist") != "lognormal":
        raise ValueError(f"unknown distribution {dist!r}")
    mu, sigma = math.log(dist["median"]), dist["sigma"]
    nd = NormalDist()
    lo = nd.cdf((math.log(dist["min"]) - mu) / sigma)
    hi = nd.cdf((math.log(dist["max"]) - mu) / sigma)
    out = []
    for i in range(n):
        u = lo + (hi - lo) * (i + 0.5) / n
        out.append(int(round(math.exp(mu + sigma * nd.inv_cdf(u)))))
    return out


def _deal(values: List[int], stratum: int, rng) -> List[int]:
    """Lay a multiset over consecutive strata of `stratum` places so that
    every stratum holds an even sample of it: sort, deal round-robin over
    the strata, then (layout rng) shuffle inside each."""
    n = len(values)
    n_strata = max(1, math.ceil(n / stratum))
    strata: List[List[int]] = [[] for _ in range(n_strata)]
    for i, v in enumerate(sorted(values)):
        strata[i % n_strata].append(v)
    out: List[int] = []
    for s in strata:
        out.extend(int(s[j]) for j in rng.permutation(len(s)))
    return out


def _permute_strata(n: int, stratum: int, rng) -> List[int]:
    """A permutation of range(n) that only moves places inside strata."""
    order: List[int] = []
    for start in range(0, n, stratum):
        size = min(stratum, n - start)
        order.extend(start + int(j) for j in rng.permutation(size))
    return order


# -------------------------------------------------------------- kinds -----

def _closed(spec: Dict[str, Any], seed: int) -> List[Request]:
    """A working set of `documents.live` documents, each asked its quota of
    times (quotas from `asks_per_document`), one pass over the live set
    after another in one fixed order; a document whose quota is used up is
    replaced by a new one of the same length.  So a fixed share of requests
    meets a document the cache has never seen, and a document returns after
    the rest of the live set has been asked in between."""
    lay = np.random.default_rng(spec["layout_seed"])
    docs = spec["documents"]
    live, page = docs["live"], docs["align"]
    lens = [int(round((docs["len_min"] + (docs["len_max"] - docs["len_min"])
                       * i / (live - 1)) / page) * page)
            for i in range(live)]
    lo_q, hi_q = spec["asks_per_document"]
    q_lo, q_hi, q_step = spec["question_len"]
    q_values = list(range(q_lo, q_hi + 1, q_step))
    n = spec["requests"]
    # Place i of the live set: (document id, asks left).  The first
    # occupants start part-way through their quota, so that retirements
    # are spread over the passes from the start.
    quota = lambda: int(lay.integers(lo_q, hi_q + 1))
    slots = [[i, 2 + (i % (hi_q - 1))] for i in range(live)]
    next_doc = live
    reqs: List[Request] = []
    pass_order = lay.permutation(live)
    while len(reqs) < n:
        for place in pass_order:
            if len(reqs) == n:
                break
            doc, left = slots[place]
            q = q_values[len(reqs) % len(q_values)]
            reqs.append(Request(len(reqs), "list", None, lens[place] + q,
                                spec["output_tokens"], doc, lens[place]))
            if left == 1:
                slots[place] = [next_doc, quota()]
                next_doc += 1
            else:
                slots[place][1] = left - 1
    order = _permute_strata(n, spec["stratum"],
                            np.random.default_rng([seed, 1]))
    # Moving requests inside a stratum keeps the multiset, not the load's
    # timing: of two asks of one document in a stratum the first is the one
    # that misses, so the order decides which request pays a whole-prompt
    # prefill and which misses meet.  A list like that takes stratum 1 (the
    # identity), and the seed is left the token ids (README.md).
    out = [reqs[j] for j in order]
    for i, r in enumerate(out):
        r.index = i
    return out


def _open_grid(spec: Dict[str, Any], seed: int, seconds: float,
               rate_hz: Optional[float] = None) -> List[Request]:
    rate = float(rate_hz or spec["rate_hz"])
    lay = np.random.default_rng(spec["layout_seed"])
    rng = np.random.default_rng([seed, 1, 0])
    stratum = spec["stratum_slots"]
    reqs: List[Request] = []
    t0 = 0.0
    # The tail keeps the load up through the drain and a traced stretch.
    tail = spec["drain_s"] + spec.get("trace_s", 0) + 3
    for phase, length in (("ramp", spec["ramp_s"]), ("window", seconds),
                          ("tail", tail)):
        n = int(math.floor(length * rate + 1e-9))
        prompts = _deal(_quantile_midpoints(spec["prompt_len"], n),
                        stratum, lay)
        outputs = _deal(_quantile_midpoints(spec["output_len"], n),
                        stratum, lay)
        order = _permute_strata(n, stratum, rng)
        offsets = rng.random(n)
        for slot in range(n):
            j = order[slot]
            reqs.append(Request(len(reqs), phase,
                                t0 + (slot + float(offsets[slot])) / rate,
                                prompts[j], outputs[j]))
        t0 += length
    return reqs


def requests(spec: Dict[str, Any], seed: int, seconds: float,
             rate_hz: Optional[float] = None) -> List[Request]:
    """The cell's fixed list of requests, in the order they are offered."""
    if spec["kind"] == "closed":
        if spec["stratum"] > spec["documents"]["live"]:
            raise ValueError("closed: stratum may not exceed documents.live")
        return _closed(spec, seed)
    if spec["kind"] == "open_grid":
        return _open_grid(spec, seed, seconds, rate_hz)
    raise ValueError(f"kind {spec['kind']!r} offers no requests")


def fill_tokens(reqs: List[Request], seed: int, vocab: int) -> None:
    """Token ids from the seed: each document's text once (so that every
    ask of it shares the prefix), then each request's own tokens."""
    doc_text: Dict[int, np.ndarray] = {}
    for r in reqs:
        own = np.random.default_rng([seed, 2, 0, r.index])
        if r.doc >= 0:
            if r.doc not in doc_text:
                doc_text[r.doc] = np.random.default_rng(
                    [seed, 3, r.doc]).integers(1, vocab, r.doc_len)
            q = own.integers(1, vocab, r.prompt_len - r.doc_len)
            r.tokens = np.concatenate([doc_text[r.doc], q]).tolist()
        else:
            r.tokens = own.integers(1, vocab, r.prompt_len).tolist()


def warm_shapes(spec: Dict[str, Any], max_len: int) -> Dict[str, List[int]]:
    """Prompt lengths whose prefill (and, where prefixes are shared, suffix
    prefill) programs this traffic can reach: the engine pads to powers of
    two, so one length per power of two in range."""
    def pows(lo: int, hi: int) -> List[int]:
        b, out = 8, []
        while b < lo:
            b *= 2
        while True:
            out.append(min(b, max_len))
            if b >= hi:
                return out
            b *= 2
    if spec["kind"] == "closed":
        docs = spec["documents"]
        longest = docs["len_max"] + spec["question_len"][1]
        return {"prefill": pows(docs["len_min"], longest),
                # A partly evicted document leaves any suffix length.
                "suffix": pows(1, longest - docs["align"])}
    p = spec["prompt_len"]
    return {"prefill": pows(p["min"], p["max"]), "suffix": []}
