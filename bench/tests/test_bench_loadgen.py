"""Traffic is a fixed multiset of sizes and gaps; the seed picks the order
and the tokens."""
import json
from pathlib import Path

import numpy as np
import pytest

from bench import loadgen

TRAFFIC = Path(loadgen.__file__).parent / "traffic"


def _mix(name):
    return json.loads((TRAFFIC / f"{name}.json").read_text())


@pytest.mark.parametrize("seed", [2**33 + 5, 7])
def test_same_seed_same_requests(seed):
    tr = _mix("chat")
    a = loadgen.generate(tr, 20.0, np.random.SeedSequence(seed), 1000)
    b = loadgen.generate(tr, 20.0, np.random.SeedSequence(seed), 1000)
    assert [(i.uid, i.max_new, i.due) for i in a] == \
        [(i.uid, i.max_new, i.due) for i in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))


@pytest.mark.parametrize("seeds", [(1, 2), (2**31 + 3, 2**33)])
def test_seeds_share_the_schedule_not_the_tokens(seeds):
    tr = _mix("chat")
    a = loadgen.generate(tr, 20.0, np.random.SeedSequence(seeds[0]), 1000)
    b = loadgen.generate(tr, 20.0, np.random.SeedSequence(seeds[1]), 1000)
    assert len(a) == len(b) == round(tr["arrival"]["rate_per_s"] * 20)
    assert [(len(i.prompt), i.max_new, i.due) for i in a] == \
        [(len(i.prompt), i.max_new, i.due) for i in b]
    assert not np.array_equal(a[0].prompt, b[0].prompt)
    for items in (a, b):
        dues = [i.due for i in items]
        assert dues == sorted(dues) and 0 < dues[0] and dues[-1] < 20.0
        for i in items:
            assert tr["prompt"]["min"] <= len(i.prompt) <= tr["prompt"]["max"]
            assert tr["output"]["min"] <= i.max_new <= tr["output"]["max"]
            assert i.prompt.dtype == np.int32 and i.prompt.max() < 1000
