"""The benchmark's copied traffic generator: deterministic per seed, the
same work for every seed, and lengths with no atoms."""
import sys
from collections import Counter
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import traffic  # noqa: E402

CODE = {"rate_rps": 4.0,
        "prompt_len": {"median": 1500, "sigma": 0.7, "min": 256,
                       "max": 3968},
        "output_len": {"median": 13, "sigma": 1.0, "min": 4, "max": 128}}


def _shape(items):
    return [(it.due, len(it.prompt), it.out_len) for it in items]


def test_open_loop_is_deterministic_per_seed():
    a = traffic.open_loop(CODE, 50, 2 ** 33 + 7, 49152)
    b = traffic.open_loop(CODE, 50, 2 ** 33 + 7, 49152)
    assert _shape(a) == _shape(b)
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))


def test_every_seed_gets_the_same_work_in_the_same_order():
    a = traffic.open_loop(CODE, 50, 1, 49152)
    b = traffic.open_loop(CODE, 50, 2, 49152)
    assert len(a) == len(b) == 200
    assert _shape(a) == _shape(b)
    assert not np.array_equal(a[0].prompt, b[0].prompt)
    assert a[0].due == 0.0 and max(x.due for x in a) < 50
    # the order is not sorted by length: long prompts are spread out
    lens = [len(x.prompt) for x in a]
    assert lens != sorted(lens)


@pytest.mark.parametrize("key,atom_share", [("prompt_len", 0.02),
                                            ("output_len", 0.08)])
def test_lengths_have_no_atoms(key, atom_share):
    p = CODE[key]
    xs = traffic.lognormal_quantiles(1000, p["median"], p["sigma"],
                                     p["min"], p["max"])
    c = Counter(xs)
    assert min(xs) >= p["min"] and max(xs) <= p["max"]
    # truncated, not clamped: the bounds carry no pile of mass
    assert c[p["min"]] <= atom_share * 1000 / 2
    assert c[p["max"]] <= atom_share * 1000 / 2
    assert max(c.values()) <= atom_share * 1000


def test_gaps_keep_the_mean_rate():
    g = traffic.exponential_gaps(300, 4.0)
    assert sum(g) == pytest.approx(75.0)
    assert len(set(g)) == 300


def test_closed_loop_seed_changes_token_ids_only():
    spec = {"replay": [[1600, 130], [3000, 500]]}
    a = traffic.closed_loop(spec, 1, 1000, 5)
    b = traffic.closed_loop(spec, 2, 1000, 5)
    assert [(len(x.prompt), x.out_len) for x in a] == \
        [(len(x.prompt), x.out_len) for x in b] == \
        [(1600, 130), (3000, 500)] * 2 + [(1600, 130)]
    assert not np.array_equal(a[0].prompt, b[0].prompt)


def test_requests_that_cannot_fit_are_refused_up_front():
    items = traffic.closed_loop({"replay": [[4000, 128]]}, 0, 10, 1)
    with pytest.raises(ValueError):
        traffic.check_fits(items, 4096)
