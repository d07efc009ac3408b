"""A closed backlog through the program's own front door,
``CFedRAGSystem.serve_stream``.

Traffic parameters (the traffic file): ``backlog`` (questions handed over,
more than a window finishes), ``collect_batch`` (questions per federated
round), ``answer_tokens`` (a distribution of answer budgets, drawn in
blocks so that every run of whole blocks holds the same budgets),
``instruction`` and ``check_tokens`` as for the other kinds.

The backlog is the corpus's first ``backlog`` questions, shuffled within
blocks of the budget distribution's ``block``: every run of whole blocks
holds the same questions and the same budgets.  The order is drawn once
from the traffic file's own ``seed``, the same in every run; a run's
``--seed`` draws the weights and so every served token.

The window starts with the call and ends ``seconds`` later; results that
arrive after it are not read, and the stream is closed.
"""
from __future__ import annotations

import time

import numpy as np

from bench.lib import corpus as C
from bench.lib.record import Query, response_rows, span


def plan(traffic: dict, questions: list, seconds: float) -> list[Query]:
    rng = np.random.default_rng([traffic["seed"], 2])
    n = traffic["backlog"]
    if n > len(questions) - traffic["collect_batch"]:
        raise ValueError(f"a backlog of {n} reaches the warm-up questions")
    order = C.block_order(n, traffic["answer_tokens"]["block"], rng)
    b = C.budgets(traffic["answer_tokens"], n, rng)
    return [
        Query(index=int(i), text=C.question_text(questions[i], traffic["instruction"]), budget=b[j])
        for j, i in enumerate(order)
    ]


def warm(dep, traffic: dict, questions: list) -> None:
    """The round batch from questions the window never asks (the plan
    leaves the last ``collect_batch`` out), then the engine's programs."""
    from bench.lib import deploy

    n = traffic["collect_batch"]
    deploy.warm_collect(dep, [C.question_text(q, traffic["instruction"]) for q in questions[-n:]])
    deploy.warm_engine(dep)


def drive(dep, traffic: dict, queries: list[Query], run) -> None:
    system = dep.system
    orch = system.orchestrator
    by_text = {q.text: q for q in queries}
    collect = orch.collect_contexts_batch

    def recorded_collect(texts, **kw):
        start = time.monotonic()
        with span("bench.round"):
            responses = collect(texts, **kw)
        run.rounds.append((start, time.monotonic(), len(texts)))
        for b, t in enumerate(texts):
            by_text[t].responses = response_rows(responses, b)
        return responses

    orch.collect_contexts_batch = recorded_collect
    run.queries = queries
    run.t0 = time.monotonic()
    run.t1 = run.t0 + run.seconds
    stream = system.serve_stream(
        [q.text for q in queries], max_new_tokens=[q.budget for q in queries],
        collect_batch=traffic["collect_batch"],
    )
    try:
        for i, res in stream:
            now = time.monotonic()
            if now > run.t1:
                break
            q = queries[i]
            q.answered = now
            if res["status"] == "done" and not res.get("truncated"):
                q.status = "done"
                q.answer = np.asarray(res["answer_tokens"])
                q.prompt = np.asarray(res["prompt"])[0]
            else:
                q.status = "failed"
    finally:
        stream.close()
        orch.collect_contexts_batch = collect
    run.t_end = time.monotonic()
