"""Per-request TTFT and TPOT arithmetic and the host-clock readers, on a
hand-built record, with requests cut off when the window closes."""
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import run  # noqa: E402
import timing  # noqa: E402


def _rec():
    cell = run.Cell(name="x", chips=1, config={}, traffic={}, metrics=[])
    rec = run.RunRecord(cell=cell, seed=0, seconds=10.0, loop="open",
                        t_open=100.0, t_close=110.0)
    P = np.zeros(50, np.int32)

    def add(rid, due, times, out_len, rejected=False):
        r = run.ReqRecord(rid=rid, due=due, prompt=P, out_len=out_len,
                          times=list(times),
                          tokens=list(range(len(times))), rejected=rejected)
        rec.requests[rid] = r

    add(0, 100.0, [100.5, 100.5, 101.5, 102.5], 4)   # done: 4 tokens
    add(1, 101.0, [103.0, 103.0, 104.0], 8)          # running at close
    add(2, 108.0, [], 4)                             # no token by close
    add(3, 109.0, [109.5, 109.5, 112.0], 4)          # tokens after close
    add(4, 99.0, [99.5, 100.1], 2)                   # due before open
    add(5, 105.0, [], 4, rejected=True)              # refused
    return rec


def test_ttft_counts_cut_off_requests_to_the_close():
    rec = _rec()
    assert sorted(timing.ttft_s(rec)) == pytest.approx(
        [0.5, 0.5, 2.0, 2.0])


def test_tpot_averages_each_requests_gaps_so_far():
    rec = _rec()
    # rid 0: (102.5 - 100.5) / 3; rid 1: 1.0 / 2; rid 3: 0 / 1 (its
    # third token came after the close); rid 2 has no gap
    assert sorted(timing.tpot_s(rec)) == pytest.approx(
        [0.0, 0.5, 2.0 / 3])


def test_percentile_readers():
    rec = _rec()
    ttft = run.metric_reader("ttft_p90_ms")(rec)
    assert ttft == pytest.approx(1e3 * np.percentile([0.5, 0.5, 2, 2], 90))
    tpot = run.metric_reader("tpot_p95_ms")(rec)
    assert tpot == pytest.approx(1e3 * np.percentile([0, 0.5, 2 / 3], 95))
    assert timing.percentile_ms([], 90) is None


def test_output_rate_counts_tokens_inside_the_window():
    rec = _rec()
    # 4 + 3 + 2 (rid 3 before the close) + 1 (rid 4 at 100.1)
    assert run.metric_reader("output_tok_per_s")(rec) == pytest.approx(1.0)


def test_step_readers():
    rec = _rec()
    S = run.StepRecord
    rec.steps = [
        S(99.0, 99.5, [4], {4: 2}, [51]),          # set-up: not counted
        S(100.0, 100.5, [0], {0: 2, 4: 1}, [51, 52]),
        S(101.0, 101.5, [], {0: 1}, [52]),
        S(102.5, 103.0, [1], {0: 1, 1: 2}, [53, 51]),
        S(109.0, 109.5, [3], {3: 2}, [51]),
    ]
    rec.prefill_tokens = 4 * 64
    # gaps: step 2: 0's intra-step gap (stalled? no other admission) and
    # 4's gap (stalled by 0); step 3: 0 (no); step 4: 0 (stalled by 1),
    # 1's intra-step gap (no); step 5: 3's intra-step gap (no)
    assert run.metric_reader("stalled_gap_share")(rec) == \
        pytest.approx(100 * 2 / 6)
    # three admissions of 50 real tokens in 64-token buckets; the
    # set-up admission is outside the window but so is its prefill
    rec.prefill_tokens = 3 * 64
    assert run.metric_reader("prefill_pad_share")(rec) == \
        pytest.approx(100 * (192 - 150) / 192)


def test_readers_are_found_by_name_and_by_stem():
    assert run.metric_reader("decode_step_ms.serve") is not None
    with pytest.raises(FileNotFoundError):
        run.metric_reader("no_such_metric")
