"""The traffic generator: every seed offers the same work, and a backlog
window opens on a batch spread over its outputs as steady serving holds
it."""
import numpy as np

import traffic
from conftest import BENCH

DECODE = traffic.load(f"{BENCH}/traffic/decode.json")


def test_seed_draws_only_token_ids():
    a = traffic.Generator(DECODE, 1, 49155)
    b = traffic.Generator(DECODE, 2 ** 31 + 7, 49155)
    for i in range(40):
        oa, ob = a.offer(i), b.offer(i)
        assert (oa.prompt.size, oa.max_new_tokens) == \
            (ob.prompt.size, ob.max_new_tokens)
        assert not np.array_equal(oa.prompt, ob.prompt)


def test_in_flight_batch_is_spread_over_one_output():
    g = traffic.Generator(DECODE, 5, 49155)
    batch = 16
    spacing = g.outputs.mean() / batch
    for i in range(batch):
        o, f = g.offer(i), g.in_flight(i, batch)
        # the request's own prompt, then what it has served so far
        assert np.array_equal(f.prompt[:o.prompt.size], o.prompt)
        assert f.prompt.size + f.max_new_tokens == \
            o.prompt.size + o.max_new_tokens
        assert f.max_new_tokens == min(o.max_new_tokens,
                                       round((i + 0.5) * spacing))
    left = [g.in_flight(i, batch).max_new_tokens for i in range(4)]
    assert left == sorted(left) and left[0] >= 1
