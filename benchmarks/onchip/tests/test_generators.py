import json
import os

import numpy as np
import pytest

from harness.generators import closed, open as open_gen, train
from harness.manifest import BENCH_DIR


def _traffic(name):
    with open(os.path.join(BENCH_DIR, "traffic", name + ".json")) as f:
        return json.load(f)


def test_open_loop_schedule_is_the_same_for_every_seed_and_only_token_ids_differ():
    t = _traffic("chat-open")
    a = open_gen.plan(t, 32000, seed=1, seconds=50.0)
    b = open_gen.plan(t, 32000, seed=2**31 + 77, seconds=50.0)
    ma, mb = [r for r in a.requests if r.measured], [r for r in b.requests if r.measured]
    assert len(ma) == len(mb) == round(t["rate_per_s"] * 50.0)
    shape = lambda rs: [(len(r.prompt), r.max_new_tokens, r.due_s) for r in rs]  # noqa: E731
    assert shape(a.requests) == shape(b.requests)          # same sizes at the same instants
    assert a.requests[0].prompt != b.requests[0].prompt    # other token ids
    assert ma[-1].due_s == pytest.approx(50.0)
    assert all(len(r.prompt) + r.max_new_tokens <= 2048 for r in a.requests)
    assert all(64 <= len(r.prompt) <= 1792 and 32 <= r.max_new_tokens <= 256 for r in ma)
    lead = [r for r in a.requests if not r.measured]
    assert lead and all(-t["lead_in_s"] <= r.due_s <= 0.0 for r in lead)
    assert [len(r.prompt) for r in lead] == [len(r.prompt) for r in ma[-len(lead):]]  # the schedule's own tail


def test_the_schedule_is_the_quantile_multiset_in_the_files_order():
    t = _traffic("chat-open")
    from harness.generators.dists import quantile_values

    m = [r for r in open_gen.plan(t, 32000, seed=3, seconds=50.0).requests if r.measured]
    n = round(t["rate_per_s"] * 50.0)  # the file's rate decides how many requests a window holds
    assert len(m) == n >= 100          # ten or more beyond the p90
    assert sorted(len(r.prompt) for r in m) == sorted(quantile_values(t["prompt_tokens"], n).tolist())
    assert [len(r.prompt) for r in m] != sorted(len(r.prompt) for r in m)
    other = dict(t, order_seed=t.get("order_seed", 0) + 1)
    m2 = [r for r in open_gen.plan(other, 32000, seed=3, seconds=50.0).requests if r.measured]
    assert [len(r.prompt) for r in m2] != [len(r.prompt) for r in m]
    assert sorted(len(r.prompt) for r in m2) == sorted(len(r.prompt) for r in m)
    gaps = np.diff([0.0] + [r.due_s for r in m])
    assert gaps.sum() == pytest.approx(50.0) and gaps.std() / gaps.mean() > 1.0  # burstier than Poisson


def test_same_seed_same_requests():
    t = _traffic("chat-open")
    a, b = (open_gen.plan(t, 32000, seed=9, seconds=20.0) for _ in range(2))
    assert [(r.due_s, r.prompt, r.max_new_tokens) for r in a.requests] == \
           [(r.due_s, r.prompt, r.max_new_tokens) for r in b.requests]


def test_closed_loop_list_is_fixed_and_token_ids_are_seeded():
    t = _traffic("batch-closed")
    a, b = closed.plan(t, 32000, 3, 45.0), closed.plan(t, 32000, 4, 45.0)
    assert list(a.prompt_lens) == list(b.prompt_lens) and list(a.output_lens) == list(b.output_lens)
    assert list(a.prompt_lens) != sorted(a.prompt_lens)
    assert a.clients == 16 and 256 <= min(a.prompt_lens) and max(a.prompt_lens) <= 1024
    p, n = a.next_request()
    assert len(p) == a.prompt_lens[0] and n == a.output_lens[0] and len(p) + n <= 2048
    assert b.next_request()[0] != p


def test_train_batches_are_seeded_and_rows_differ():
    t = _traffic("train-8k")
    a, b = train.plan(t, 32000, 5, 45.0, rows=4), train.plan(t, 32000, 5, 45.0, rows=4)
    assert a.shape == (1, 4, 8192) and a.tokens_per_step == 4 * 8192
    np.testing.assert_array_equal(a.batch(3), b.batch(3))
    assert not np.array_equal(a.batch(3), a.batch(4))
    x = a.batch(0)[0]
    assert len({row.tobytes() for row in x}) == 4
    assert not np.array_equal(train.plan(t, 32000, 6, 45.0, rows=4).batch(0), a.batch(0))
