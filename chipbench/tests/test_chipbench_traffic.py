"""The traffic generator is deterministic per seed, keeps to its mix's
parameters, and replays the same sizes and arrivals under every seed."""

import json
import sys
from collections import Counter
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT)]

from chipbench.traffic import Traffic, load_mix  # noqa: E402

MIXES = sorted((ROOT / "chipbench" / "traffic").glob("*.json"))
BIG_SEED = 2 ** 31 + 12345


@pytest.mark.parametrize("path", MIXES, ids=lambda p: p.stem)
def test_same_seed_same_requests(path):
    mix = load_mix(path)
    a, b = Traffic(mix, BIG_SEED), Traffic(mix, BIG_SEED)
    for i in (0, 1, mix["block"] - 1, mix["block"] + 3):
        ra, rb = a.request(i), b.request(i)
        assert np.array_equal(ra.prompt, rb.prompt)
        assert (ra.max_new_tokens, ra.due_s) == (rb.max_new_tokens, rb.due_s)
    assert not np.array_equal(Traffic(mix, 7).request(0).prompt,
                              a.request(0).prompt)


@pytest.mark.parametrize("path", MIXES, ids=lambda p: p.stem)
def test_requests_within_parameters(path):
    mix = load_mix(path)
    t = Traffic(mix, 3)
    pre, suf, out = mix["prefixes"], mix["suffix"], mix["output"]
    assert pre["len_min"] <= t.prefix_lens.min()
    assert t.prefix_lens.max() <= pre["len_max"]
    for r in t.requests(mix["block"]):
        k = r.prefix
        n_suffix = len(r.prompt) - t.prefix_lens[k]
        assert suf["min"] <= n_suffix <= suf["max"]
        assert out["min"] <= r.max_new_tokens <= out["max"]
        assert np.array_equal(r.prompt[:t.prefix_lens[k]],
                              t.prefix_tokens(k))
        assert r.prompt.min() >= 0 and r.prompt.max() < mix["vocab"]
    fill = mix["fill"]
    assert t.due_s(fill - 1) == 0.0
    due = [t.due_s(fill + k) for k in range(mix["arrival"]["backlog"] + 20)]
    assert due == sorted(due) and due[-1] > 0


def test_steady_arrivals():
    from chipbench.arrivals import steady

    params = {"backlog": 3, "rate_per_s": 4.0}
    assert [steady.due_s(k, params) for k in range(3)] == [0.0] * 3
    assert steady.due_s(3, params) == pytest.approx(0.25)
    assert steady.due_s(12, params) == pytest.approx(2.5)


@pytest.mark.parametrize("path", MIXES, ids=lambda p: p.stem)
def test_every_seed_replays_the_same_sizes(path):
    """Only token ids depend on the seed: documents, lengths, their order
    and the arrivals are fixed."""
    mix = load_mix(path)
    n = 2 * mix["block"] + 5

    def sizes(seed):
        t = Traffic(mix, seed)
        rs = t.requests(n)
        return ([r.prefix for r in rs],
                [len(r.prompt) - t.prefix_lens[r.prefix] for r in rs],
                [r.max_new_tokens for r in rs], [r.due_s for r in rs])

    a, b = sizes(11), sizes(2 ** 31 + 5)
    assert a == b
    block = mix["block"]
    assert Counter(a[0][:block]) == Counter(a[0][block:2 * block])
    assert a[0][:block] != a[0][block:2 * block]


def test_popular_documents_come_first():
    mix = load_mix(MIXES[0])
    t = Traffic(mix, 5)
    counts = Counter(r.prefix for r in t.requests(mix["block"]))
    assert counts[0] == max(counts.values())
    assert json.dumps(mix)
