"""Detector precision on synthesized heat maps — one test per paper pattern."""

import numpy as np
import pytest

from repro.core.heatmap import Analyzer
from repro.core.patterns import (
    FALSE_SHARING,
    HOT,
    HOT_RANDOM,
    MISALIGNMENT,
    SCRATCH_ABUSE,
    STRIDED,
    detect_all,
)
from repro.core.tiles import TileGeometry
from repro.core.trace import AccessRecord, RegionInfo, TraceBuffer


def _heatmap(records, shape=(64, 256), space="hbm", n_programs=8):
    buf = TraceBuffer()
    geom = TileGeometry(shape=shape, itemsize=4, name="A")
    buf.register_region(RegionInfo("A", geom, space=space))
    for pid, touches in records:
        buf.append(
            AccessRecord(array="A", site="k/A", space=space, kind="load",
                         program_id=(pid,), touches=tuple(touches)))
    an = Analyzer("k", (n_programs,), "full")
    an.ingest(buf)
    return an.flush()


def _patterns(hm):
    return {r.pattern for r in detect_all(hm)}


def test_hot_detected():
    # every program touches every word of every sector (uniform hot)
    recs = [(p, [(t, w) for t in range(8) for w in range(8)]) for p in range(8)]
    assert HOT in _patterns(_heatmap(recs))


def test_hot_random_detected():
    rng = np.random.default_rng(1)
    recs = []
    for p in range(16):
        touches = []
        for t in range(8):
            # random subsets of words, multiple warm words/sector
            ws = rng.choice(8, size=rng.integers(2, 6), replace=False)
            if rng.random() < 0.7:
                touches += [(t, int(w)) for w in ws]
        recs.append((p, touches))
    pats = _patterns(_heatmap(recs, n_programs=16))
    assert HOT_RANDOM in pats or HOT in pats


def test_false_sharing_detected():
    # 8 programs each own one word of each sector
    recs = [(p, [(t, p) for t in range(8)]) for p in range(8)]
    pats = _patterns(_heatmap(recs))
    assert FALSE_SHARING in pats
    assert STRIDED not in pats


def test_strided_detected():
    # all programs hit word 0 of every sector; words 1-7 cold
    recs = [(p, [(t, 0) for t in range(16)]) for p in range(8)]
    pats = _patterns(_heatmap(recs, shape=(128, 256)))
    assert STRIDED in pats
    assert FALSE_SHARING not in pats


def test_misalignment_detected():
    # every program reads 8 words starting at word 4 of its tile: head-4
    # words of the NEXT tile get one extra contributor
    recs = []
    for p in range(8):
        touches = [(p, w) for w in range(4, 8)] + [(p + 1, w) for w in range(4)]
        recs.append((p, touches))
    pats = _patterns(_heatmap(recs, shape=(80, 128), n_programs=8))
    assert MISALIGNMENT in pats


def test_scratch_abuse_detected():
    # scratch where each word is touched by exactly one program
    recs = [(p, [(0, p)]) for p in range(8)]
    hm = _heatmap(recs, shape=(8, 128), space="vmem_scratch")
    reports = [r for r in detect_all(hm) if r.pattern == SCRATCH_ABUSE]
    assert reports and reports[0].severity >= 0.75


def test_scratch_shared_not_flagged():
    # scratch where everyone touches everything: proper shared use
    recs = [(p, [(0, w) for w in range(8)]) for p in range(8)]
    hm = _heatmap(recs, shape=(8, 128), space="vmem_scratch")
    assert SCRATCH_ABUSE not in _patterns(hm)


def test_coalesced_clean():
    # one program per sector touching all words: no pattern at all
    recs = [(p, [(p, w) for w in range(8)]) for p in range(8)]
    assert _patterns(_heatmap(recs)) == set()


def test_advisor_ranks_by_saving():
    from repro.core.advisor import advise

    recs = [(p, [(t, p) for t in range(8)]) for p in range(8)]
    hm = _heatmap(recs)
    actions = advise(hm)
    assert actions and actions[0].kind == "retile"
    assert actions[0].est_transaction_saving > 0.5
