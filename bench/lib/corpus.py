"""The benchmark's corpus and question generator.

Stand-in for MedCorp and MIRAGE, made from the seed.  Four corpora over two
sites (the source paper's topology); facts are ``entity attribute value``
triples hidden in topic words; each question asks for one fact's value
among four options.  Chunk lengths follow a lognormal distribution given by
the configuration (MedCorp snippets run to a few hundred words), cut so
that the fact always survives the provider's ``chunk_max_len`` tokens.

The corpus is the deployment's data: it is made from the configuration's
own ``seed``, the same for every run, as the traffic's schedule is made
from the traffic file's seed; a run's seed draws the weights.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist

import numpy as np

CORPORA = ("pubmed", "wikipedia", "statpearls", "textbooks")
SITE_OF = {"pubmed": 0, "wikipedia": 0, "statpearls": 1, "textbooks": 1}
# share of facts per corpus: pubmed dominates, as in the paper's Table 1
CORPUS_WEIGHTS = (0.55, 0.15, 0.15, 0.15)
TOPIC_WORDS = 200
N_ATTRS = 32
LETTERS = "ABCD"


@dataclasses.dataclass
class Chunk:
    text: str
    corpus: str
    site: int
    chunk_id: int
    fact_id: int  # -1 for distractor chunks


@dataclasses.dataclass
class Question:
    question: str  # "what is <attr> of <entity>"
    options: list[str]
    answer: str  # the correct option's letter
    gold_chunk_id: int
    corpus: str


def block_order(n: int, block: int, rng: np.random.Generator) -> np.ndarray:
    """0..n-1 shuffled within consecutive blocks of ``block``: any run of
    whole blocks holds the same items."""
    return np.concatenate([rng.permutation(np.arange(i, min(i + block, n))) for i in range(0, n, block)])


def stratified(ppf, n: int, block: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` draws of a distribution given by its quantile function, in
    blocks of ``block``: every block holds the same ``block`` quantiles
    (at probabilities (i + 1/2) / block), shuffled within the block.  Any
    run of whole blocks therefore holds the same multiset of values."""
    q = np.asarray([ppf((i + 0.5) / block) for i in range(block)])
    n_blocks = -(-n // block)
    return np.concatenate([rng.permutation(q) for _ in range(n_blocks)])[:n]


def lognormal_ppf(median: float, sigma: float, lo: float, hi: float):
    nd = NormalDist()
    return lambda u: min(max(median * math.exp(sigma * nd.inv_cdf(u)), lo), hi)


def uniform_int_ppf(lo: int, hi: int):
    return lambda u: lo + min(int(u * (hi - lo + 1)), hi - lo)


def budgets(spec: dict, n: int, rng, block: int | None = None) -> list[int]:
    """``n`` answer budgets from the traffic's distribution, in blocks of
    ``block`` (default: the spec's ``block``, or one of each value of a
    uniform integer range)."""
    if spec["dist"] == "uniform_int":
        ppf = uniform_int_ppf(spec["lo"], spec["hi"])
        block = block or spec.get("block") or spec["hi"] - spec["lo"] + 1
    else:
        ppf = lognormal_ppf(spec["median"], spec["sigma"], spec["lo"], spec["hi"])
        block = block or spec["block"]
    return [int(round(b)) for b in stratified(ppf, n, block, rng)]


def make_corpus(spec: dict) -> tuple[list[Chunk], list[Question]]:
    """``spec``: the configuration's ``corpus`` group (seed, n_facts,
    n_distractors, chunk_words_median, chunk_words_sigma, chunk_max_len)."""
    rng = np.random.default_rng([spec["seed"], 1])
    n_facts, n_dis = int(spec["n_facts"]), int(spec["n_distractors"])
    max_words = int(spec["chunk_max_len"]) - 2  # BOS and EOS take two tokens
    ppf = lognormal_ppf(spec["chunk_words_median"], spec["chunk_words_sigma"], 8, max_words)
    n_words = np.rint(stratified(ppf, n_facts + n_dis, n_facts + n_dis, rng)).astype(int)
    topics = {c: [f"{c}word{i}" for i in range(TOPIC_WORDS)] for c in CORPORA}
    attrs = [f"attr{i}" for i in range(N_ATTRS)]

    counts = [int(round(w * n_facts)) for w in CORPUS_WEIGHTS]
    counts[0] += n_facts - sum(counts)
    fact_corpus = rng.permutation(np.repeat(np.arange(len(CORPORA)), counts))
    chunks: list[Chunk] = []
    facts = []
    for f in range(n_facts):
        corpus = CORPORA[fact_corpus[f]]
        ent, attr = f"entity{f}", attrs[rng.integers(N_ATTRS)]
        val = f"value{f}x{rng.integers(10_000)}"
        filler = " ".join(rng.choice(topics[corpus], size=n_words[f] - 5))
        chunks.append(Chunk(f"{filler} {ent} {attr} is {val} .", corpus, SITE_OF[corpus], f, f))
        facts.append((ent, attr, val, corpus))
    dis_corpus = rng.permutation(np.arange(n_dis) % len(CORPORA))
    for j in range(n_dis):
        corpus = CORPORA[dis_corpus[j]]
        text = " ".join(rng.choice(topics[corpus], size=n_words[n_facts + j]))
        chunks.append(Chunk(text, corpus, SITE_OF[corpus], len(chunks), -1))

    questions = []
    for f in rng.permutation(n_facts):
        ent, attr, val, corpus = facts[f]
        others = rng.choice(n_facts - 1, size=len(LETTERS) - 1, replace=False)
        options = [val] + [facts[o + (o >= f)][2] for o in others]
        order = rng.permutation(len(LETTERS))
        options = [options[o] for o in order]
        questions.append(Question(
            question=f"what is {attr} of {ent}", options=options,
            answer=LETTERS[int(np.argmax(order == 0))], gold_chunk_id=int(f), corpus=corpus,
        ))
    return chunks, questions


def question_text(q: Question, instruction: str) -> str:
    """The text a user sends: the question first (retrieval reads the first
    words), then the options, then the answer instruction."""
    opts = " ".join(f"{l} . {o}" for l, o in zip(LETTERS, q.options))
    return f"{q.question} ? options {opts} . {instruction}"
