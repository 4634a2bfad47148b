"""The seeded traffic: same seed same stream, every seed the same work in
the same order, with its own prompt tokens."""
from __future__ import annotations

import json
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import traffic  # noqa: E402

MIXES = sorted(p.stem for p in (ROOT / "bench" / "traffic").glob("*.json"))


def mix(name):
    return json.loads((ROOT / "bench" / "traffic" / f"{name}.json").read_text())


def items(stream, a, b):
    return [stream.item(i) for i in range(a, b)]


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_stream(name):
    m = mix(name)
    a, b = traffic.Stream(m, 2**40 + 3), traffic.Stream(m, 2**40 + 3)
    assert items(a, 0, 3 * m["pool"]) == items(b, 0, 3 * m["pool"])
    assert a.prompt(a.item(5), 1000) == b.prompt(b.item(5), 1000)


@pytest.mark.parametrize("name", MIXES)
def test_other_seed_same_work_other_order(name):
    """Another seed: the same lengths in the same order, other tokens; each
    cycle holds the same requests as the first, in another order."""
    m = mix(name)
    n = m["pool"]
    a, b = traffic.Stream(m, 1), traffic.Stream(m, 2)
    assert items(a, 0, 3 * n) == items(b, 0, 3 * n)
    assert a.prompt(a.item(0), 1000) != b.prompt(b.item(0), 1000)
    key = lambda it: (it.prompt_len, it.output_len)  # noqa: E731
    for c in range(1, 3):
        assert sorted(items(a, c * n, (c + 1) * n), key=key) == sorted(items(a, 0, n), key=key)
    assert items(a, 0, n) != items(a, n, 2 * n)


@pytest.mark.parametrize("name", MIXES)
def test_medians_and_clips_hold(name):
    m = mix(name)
    s = traffic.Stream(m, 7)
    for lens, spec in ((s.prompt_lens, m["prompt"]), (s.output_lens, m["output"])):
        assert lens.min() >= spec["min"] and lens.max() <= spec["max"]
        assert abs(np.median(lens) / spec["median"] - 1) < 0.02
    assert m["prompt"]["max"] + m["output"]["max"] <= m["engine"]["max_context"]
