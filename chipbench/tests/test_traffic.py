"""Every seed gets the same work; the seed draws the token ids."""

import numpy as np
import pytest

from chipbench import traffic


@pytest.mark.parametrize("name", ["decode_closed", "chat_open"])
def test_every_block_holds_the_same_lengths(name):
    mix = traffic.load(name)
    a = traffic.Generator(mix, 1000, seed=1)
    b = traffic.Generator(mix, 1000, seed=2**31 + 5)
    B = traffic.BLOCK
    # the same lengths in the same order for every seed, each block the same
    # set of quantiles in its own order
    assert np.array_equal(a.prompts, b.prompts) and np.array_equal(a.outs, b.outs)
    for i in range(B, 4 * B, B):
        assert sorted(a.prompts[i:i + B]) == sorted(a.prompts[:B])
        assert sorted(a.outs[i:i + B]) == sorted(a.outs[:B])
    assert not np.array_equal(a.prompts[:B], a.prompts[B:2 * B])
    lo, hi = mix["prompt_len"]["low"], mix["prompt_len"]["high"]
    assert a.prompts.min() >= lo and a.prompts.max() <= hi
    # what the seed does draw: the token ids
    ra, rb = a.make(), b.make()
    assert len(ra.prompt) == len(rb.prompt) and not np.array_equal(ra.prompt, rb.prompt)


def test_open_schedule_blocks_share_their_gaps():
    mix = traffic.load("chat_open")
    rate = mix["arrivals"]["rate_per_s"]
    B = traffic.BLOCK
    dues = []
    for seed in (3, 4):
        items = traffic.open_schedule(traffic.Generator(mix, 1000, seed), 200.0)
        due = np.asarray([it.due for it in items])
        assert np.all(np.diff(due) > 0) and due[-1] < 200.0
        dues.append(due)
    # the same arrivals for every seed: blocks of B stratified exponential
    # quantiles, mean gap close to 1 / rate
    assert np.array_equal(dues[0], dues[1])
    assert dues[0][B - 1] / B == pytest.approx(1.0 / rate, rel=0.1)
