"""The one general generator, over every traffic file that has arrivals."""
import glob
import json
import os

import pytest

from perfbench import traffic

HERE = os.path.dirname(os.path.abspath(__file__))
FILES = sorted(glob.glob(os.path.join(HERE, '..', 'traffic', '*.json')))
SERVING = [f for f in FILES if 'rate_per_s' in json.load(open(f))]
BIG_SEED = 2**31 + 12345      # the driver's seeds pass 32 signed bits


@pytest.fixture(params=SERVING, ids=[os.path.basename(f) for f in SERVING])
def mix(request):
    with open(request.param, encoding='utf-8') as f:
        return json.load(f)


def test_every_traffic_file_names_a_runner():
    assert FILES and SERVING
    for path in FILES:
        with open(path, encoding='utf-8') as f:
            runner = json.load(f)['runner']
        assert os.path.exists(os.path.join(HERE, '..', 'runners',
                                           runner + '.py'))


def test_same_seed_same_schedule(mix):
    a = traffic.schedule(mix, BIG_SEED, 30)
    assert a == traffic.schedule(mix, BIG_SEED, 30)
    assert traffic.prompt_ids(a[0], 1000) == traffic.prompt_ids(a[0], 1000)


def test_another_seed_draws_other_tokens_for_the_same_schedule(mix):
    a = traffic.schedule(mix, BIG_SEED, 30)
    b = traffic.schedule(mix, BIG_SEED + 1, 30)
    assert a != b
    assert [r[:3] for r in a] == [r[:3] for r in b]     # due, lengths
    assert traffic.prompt_ids(a[0], 1000) != traffic.prompt_ids(b[0], 1000)


def test_lengths_inside_their_clips_and_due_inside_the_window(mix):
    reqs = traffic.schedule(mix, 7, 30)
    for r in reqs:
        assert (mix['prompt_tokens']['min'] <= r.prompt_tokens
                <= mix['prompt_tokens']['max'])
        assert (mix['output_tokens']['min'] <= r.output_tokens
                <= mix['output_tokens']['max'])
        assert 0.0 < r.due_s < 30.0
    assert [r.due_s for r in reqs] == sorted(r.due_s for r in reqs)


def test_offered_rate_over_10000_draws(mix):
    seconds = 10000 / mix['rate_per_s']
    reqs = traffic.schedule(mix, 3, seconds)
    assert len(reqs) == pytest.approx(10000, abs=1)
    rate = len(reqs) / (reqs[-1].due_s - reqs[0].due_s)
    assert rate == pytest.approx(mix['rate_per_s'], rel=0.05)
    lengths = sorted(r.prompt_tokens for r in reqs)
    assert lengths[len(lengths) // 2] == pytest.approx(
        mix['prompt_tokens']['median'], rel=0.02)
