"""The traffic generators: deterministic per seed, the configured
distributions, and the same sizes for every seed."""
import collections
import json
import math
import os

import numpy as np
import pytest

from bench.lib import corpus as C
from bench.lib import spec


def _kind(name):
    return spec.load_module(os.path.join(spec.BENCH_DIR, "kinds", name + ".py"), "t_kind_" + name)


def _traffic(name):
    with open(os.path.join(spec.BENCH_DIR, "traffic", name + ".json")) as f:
        return json.load(f)


CORPUS = {"seed": 5, "n_facts": 96, "n_distractors": 64, "chunk_words_median": 128,
          "chunk_words_sigma": 0.5, "chunk_max_len": 256}


def test_corpus_is_deterministic_per_corpus_seed():
    a, qa = C.make_corpus(dict(CORPUS, seed=7))
    b, qb = C.make_corpus(dict(CORPUS, seed=7))
    c, _ = C.make_corpus(dict(CORPUS, seed=8))
    assert [x.text for x in a] == [x.text for x in b]
    assert [q.question for q in qa] == [q.question for q in qb]
    assert [x.text for x in a] != [x.text for x in c]


def test_corpus_sizes_are_the_same_multiset_for_every_seed():
    def sizes(seed):
        chunks, _ = C.make_corpus(dict(CORPUS, seed=seed))
        return sorted(len(c.text.split()) for c in chunks), collections.Counter(c.corpus for c in chunks)

    assert sizes(1) == sizes(2 ** 31 + 11)


def test_chunk_lengths_follow_the_lognormal_and_keep_the_fact():
    chunks, questions = C.make_corpus(CORPUS)
    words = np.asarray([len(c.text.split()) for c in chunks])
    assert abs(np.median(words) - CORPUS["chunk_words_median"]) <= 3
    assert words.max() <= CORPUS["chunk_max_len"] - 2
    for q in questions[:20]:
        gold = chunks[q.gold_chunk_id]
        ent, attr = q.question.split()[-1], q.question.split()[2]
        assert f"{ent} {attr} is" in gold.text


@pytest.mark.parametrize("spec_", [
    {"dist": "uniform_int", "lo": 4, "hi": 16},
    {"dist": "lognormal", "median": 192, "sigma": 0.6, "lo": 64, "hi": 512, "block": 16},
])
def test_answer_budgets_follow_the_distribution_in_blocks(spec_):
    rng = np.random.default_rng(0)
    b = np.asarray(C.budgets(spec_, 64, rng))
    assert b.min() >= spec_["lo"] and b.max() <= spec_["hi"]
    block = spec_.get("block", spec_["hi"] - spec_["lo"] + 1)
    first = sorted(b[:block])
    for k in range(1, len(b) // block):
        assert sorted(b[k * block : (k + 1) * block]) == first
    if spec_["dist"] == "uniform_int":
        assert first == list(range(4, 17))
    else:
        assert abs(np.median(b) - spec_["median"]) / spec_["median"] < 0.1


def test_open_loop_arrivals_are_poisson_gaps_spanning_the_window():
    kind, traffic = _kind("open_poisson"), _traffic("rag-mc")
    _, questions = C.make_corpus(CORPUS)
    traffic = dict(traffic, rate_qps=2.0)
    plan = kind.plan(traffic, questions, 40.0)
    again = kind.plan(traffic, questions, 40.0)
    other = kind.plan(dict(traffic, seed=traffic["seed"] + 1), questions, 40.0)
    due = np.asarray([q.due for q in plan])
    assert len(plan) == 80 and due[0] == 0.0 and due[-1] < 40.0
    # the schedule is the traffic file's: the same trace in every run
    assert [(q.text, q.due, q.budget) for q in plan] == [(q.text, q.due, q.budget) for q in again]
    assert [q.due for q in plan] != [q.due for q in other]
    gaps = np.diff(due)
    # exponential gaps: the coefficient of variation is near 1
    assert 0.7 < gaps.std() / gaps.mean() < 1.3
    # another schedule seed has the same gaps and budgets, in another order
    def all_gaps(p):
        d = [q.due for q in p]
        return np.sort(np.append(np.diff(d), 40.0 - d[-1]))

    np.testing.assert_allclose(all_gaps(plan), all_gaps(other), rtol=1e-9)
    assert sorted(q.budget for q in plan) == sorted(q.budget for q in other)
    assert len({q.text for q in plan}) == len(plan)
    assert {q.text for q in plan} == {q.text for q in other}


def test_closed_backlog_budgets_repeat_per_block_and_skip_the_warm_up_questions():
    kind, traffic = _kind("closed_backlog"), _traffic("eval-cot")
    _, questions = C.make_corpus(CORPUS)
    traffic = dict(traffic, backlog=64)
    plan = kind.plan(traffic, questions, 40.0)
    assert [(q.text, q.budget) for q in plan] == [(q.text, q.budget) for q in kind.plan(traffic, questions, 40.0)]
    warm = {C.question_text(q, traffic["instruction"]) for q in questions[-traffic["collect_batch"]:]}
    assert not warm & {q.text for q in plan}
    blk = traffic["answer_tokens"]["block"]
    assert sorted(q.budget for q in plan[:blk]) == sorted(q.budget for q in plan[blk : 2 * blk])
    other = kind.plan(dict(traffic, seed=traffic["seed"] + 1), questions, 40.0)
    assert [q.text for q in plan] != [q.text for q in other]
    for k in range(0, 64, blk):
        assert {q.text for q in plan[k : k + blk]} == {q.text for q in other[k : k + blk]}
    assert math.isclose(np.median([q.budget for q in plan]), 192, rel_tol=0.1)
