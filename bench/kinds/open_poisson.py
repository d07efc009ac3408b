"""Open-loop arrivals through the benchmark's own front door.

Traffic parameters (the traffic file): ``rate_qps`` (arrivals per second),
``answer_tokens`` (a distribution of answer budgets), ``seed`` (of the
schedule), ``instruction`` (the answer format the question asks for),
``check_tokens`` (served tokens the correctness check compares),
``drain_s`` (how long past the window an answer may still come).

Arrivals: the first ``rate_qps * seconds`` questions of the corpus, each
asked once, at gaps drawn from the exponential distribution by stratified
quantiles, scaled so that the gaps span the window.  The schedule (which
question arrives when, with which answer budget) is drawn once from the
traffic file's own ``seed``: it is the same trace in every run, as a
replayed log is, while a run's ``--seed`` draws the weights and so every
served token.  (Reordering the schedule by the run's seed spread the
median latency over a factor of two between seeds, while two runs of one
seed mostly agreed within 1%: the order of the arrivals, not the system,
set it.)

A collector thread takes every query that is due (up to the largest of
``ROUND_BATCHES``) into one federated round: collect, aggregate, build the
prompts, the batch padded with its last question up to the next size in
``ROUND_BATCHES``, each size warmed in set-up, so nothing compiles in the
window.  It submits each prompt to a live ``ServeEngine.serve_stream``,
anchored at its question's due time; the main thread reads answers as
the engine retires them.
"""
from __future__ import annotations

import math
import threading
import time

import numpy as np

from bench.lib import corpus as C
from bench.lib.record import Query, response_rows, span


def plan(traffic: dict, questions: list, seconds: float) -> list[Query]:
    rng = np.random.default_rng([traffic["seed"], 2])
    n = max(1, int(round(traffic["rate_qps"] * seconds)))
    gaps = C.stratified(lambda u: -math.log(1.0 - u), n, n, rng)
    # arrival i is due at the sum of the first i gaps; the n gaps span the
    # window, so the last arrival lands one gap before its end
    due = seconds * np.concatenate([[0.0], np.cumsum(gaps)[:-1]]) / gaps.sum()
    order = rng.permutation(n)
    b = C.budgets(traffic["answer_tokens"], n, rng, block=n)
    return [
        Query(index=int(i), text=C.question_text(questions[i], traffic["instruction"]),
              budget=b[j], due=float(due[j]))
        for j, i in enumerate(order)
    ]


ROUND_BATCHES = (1, 2, 4, 8)  # the batch sizes a federated round is padded to


def warm(dep, traffic: dict, questions: list) -> None:
    """A round at each of ``ROUND_BATCHES`` from questions the window never
    asks (the plan takes the first ones), then the engine's programs."""
    from bench.lib import deploy

    texts = [C.question_text(q, traffic["instruction"]) for q in questions[-ROUND_BATCHES[-1]:]]
    for n in ROUND_BATCHES:
        deploy.warm_collect(dep, texts[:n])
    deploy.warm_engine(dep)


def drive(dep, traffic: dict, queries: list[Query], run) -> None:
    """Serve the window's arrivals and drain.  Fills ``run``."""
    from repro.core.resilience import QuorumNotMet
    from repro.serving.scheduler import Scheduler

    orch, engine = dep.system.orchestrator, dep.engine
    width = engine.scfg.max_prompt_len
    sched = Scheduler()
    errors: list[BaseException] = []
    run.queries = queries
    run.t0 = time.monotonic() + 0.05
    run.t1 = run.t0 + run.seconds
    for q in queries:
        q.due += run.t0

    def collector():
        try:
            j = 0
            while j < len(queries):
                wait = queries[j].due - time.monotonic()
                if wait > 0:
                    time.sleep(wait)
                start = time.monotonic()
                batch = [k for k in range(j, min(j + ROUND_BATCHES[-1], len(queries)))
                         if queries[k].due <= start]
                j += len(batch)
                texts = [queries[k].text for k in batch]
                size = next(n for n in ROUND_BATCHES if n >= len(texts))
                texts += texts[-1:] * (size - len(texts))
                try:
                    with span("bench.round"):
                        responses = orch.collect_contexts_batch(texts)
                        contexts = orch.aggregate_batch(texts, responses)
                        prompts = [orch.build_prompt(t, c, max_len=width)
                                   for t, c in zip(texts[: len(batch)], contexts)]
                except QuorumNotMet:
                    for k in batch:
                        queries[k].status = "failed"
                    continue
                run.rounds.append((start, time.monotonic(), len(batch)))
                for b, k in enumerate(batch):
                    q = queries[k]
                    run.late.append(start - q.due)
                    q.prompt, q.responses = prompts[b][0], response_rows(responses, b)
                    sched.submit(prompts[b], max_new_tokens=q.budget, tag=k, t0=q.due)
        except BaseException as e:  # surfaced after the drain
            errors.append(e)
        finally:
            sched.close()

    thread = threading.Thread(target=collector, name="bench-collector")
    thread.start()
    stream = engine.serve_stream(sched)
    try:
        for rid, ans in stream:
            now = time.monotonic()
            req = sched.results[rid]
            q = queries[req.tag]
            q.answered, q.answer = now, np.asarray(ans)
            q.submitted, q.started, q.finished = req.submitted_at, req.started_at, req.finished_at
            q.status = "failed" if (req.truncated or req.deadlocked) else "done"
            if now > run.t1 + traffic["drain_s"]:
                break
    finally:
        stream.close()
        thread.join()
    run.t_end = time.monotonic()
    if errors:
        raise errors[0]
