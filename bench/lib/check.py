"""Whether what the timed path produced is correct, judged against the
plain reference once the window has closed and the program's state is
freed.

A sample of the queries the window finished is drawn from the seed, with
the one that has the longest answer always in it, until it holds the
traffic's ``check_tokens`` served tokens.  For each sampled query:

* retrieval, per provider: the program's scores against the exact
  (float64) cosine of the ids it returned (``score_err``), and how far
  each returned id's exact score lies below the exact score at its rank
  (``rank_gap``); a provider that did not answer fails the query;
* the prompt the engine received, against the prompt the reference lays
  out from the providers' returned ids (``prompt_diff``, exact);
* the served answer, teacher-forced through the reference model: the
  widest gap by which a served token's logit lies below the reference's
  best at its position, in standard deviations of the reference's logits
  there (``logit_gap``; greedy decoding picks the best, so a sound
  bfloat16 program reads only near-ties).

The control puts the reference in the program's place at the precision
below the configuration's (bfloat16 operands -> float8 e4m3): its
retrieval and its first-choice tokens read through the same numbers.
"""
from __future__ import annotations

import dataclasses

import jax.numpy as jnp
import numpy as np

from bench.lib import textref as T
from bench.lib import weights as W


def fp8(x):
    """Round to float8 e4m3 with one scale per tensor (max |x| -> 448)."""
    s = jnp.max(jnp.abs(x)) / 448.0
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


@dataclasses.dataclass
class Sample:
    texts: list[str]
    prompts: list[np.ndarray]  # what the engine received
    answers: list[np.ndarray]
    responses: list[list[dict]]


def sample(queries: list, check_tokens: int, seed: int) -> Sample:
    done = [q for q in queries if q.status == "done" and q.answer is not None and len(q.answer)]
    if not done:
        return Sample([], [], [], [])
    rng = np.random.default_rng([seed, 3])
    longest = max(range(len(done)), key=lambda i: (len(done[i].answer), -i))
    picked, total = [longest], len(done[longest].answer)
    for i in rng.permutation(len(done)):
        if total >= check_tokens:
            break
        if i != longest:
            picked.append(int(i))
            total += len(done[i].answer)
    qs = [done[i] for i in picked]
    return Sample([q.text for q in qs], [q.prompt for q in qs], [q.answer for q in qs],
                  [q.responses for q in qs])


class Reference:
    """The reference's view of one deployment: the corpus as token rows,
    each provider's chunk ids and its exact embeddings."""

    def __init__(self, model: dict, chunks: list):
        self.model = model
        f = model["federation"]
        self.dim, self.m_local, self.n_global = f["embed_dim"], f["m_local"], f["n_global"]
        self.width = model["serving"]["max_prompt_len"]
        self.tokens = np.stack([T.encode(c.text, max_len=model["corpus"]["chunk_max_len"]) for c in chunks])
        names = sorted({c.corpus for c in chunks})
        self.provider_ids = [np.asarray([c.chunk_id for c in chunks if c.corpus == n]) for n in names]
        self.emb = T.embed_rows(self.tokens, self.dim).astype(np.float64)
        self.emb_fp8 = None

    def retrieval(self, texts: list[str], responses: list[list[dict]], control: bool):
        """(score_err, rank_gap, providers missing) over the sample; with
        ``control`` the control's own retrieval is judged in place of the
        program's responses."""
        q_tok = np.stack([T.encode(t, max_len=T.QUERY_TOKENS) for t in texts])
        q_emb = T.embed_rows(q_tok, self.dim).astype(np.float64)
        if control:
            if self.emb_fp8 is None:
                self.emb_fp8 = np.asarray(fp8(jnp.asarray(self.emb, jnp.float32)))
            q_low = np.asarray(fp8(jnp.asarray(q_emb, jnp.float32)))
        err, gap, missing = 0.0, 0.0, 0
        for b, t in enumerate(texts):
            got = {r["provider"]: r for r in (responses[b] or [])}
            for p, ids in enumerate(self.provider_ids):
                m = min(self.m_local, len(ids))
                exact = self.emb[ids] @ q_emb[b]
                best = np.sort(exact)[::-1][:m]
                if control:
                    low = (self.emb_fp8[ids] @ q_low[b]).astype(np.float32)
                    top = np.argsort(-low, kind="stable")[:m]
                    r = {"provider": p, "scores": low[top], "chunk_ids": ids[top]}
                elif p in got:
                    r = got[p]
                else:
                    missing += 1
                    continue
                pos = np.searchsorted(ids, r["chunk_ids"])
                if len(r["chunk_ids"]) != m or not np.array_equal(ids[np.minimum(pos, len(ids) - 1)], r["chunk_ids"]):
                    missing += 1  # ids the provider does not hold, or too few
                    continue
                mine = exact[pos]
                err = max(err, float(np.abs(np.asarray(r["scores"], np.float64) - mine).max()))
                gap = max(gap, float((best - mine).max()))
        return err, gap, missing

    def prompt(self, text: str, rows: list[dict]) -> np.ndarray:
        cands = [(r["provider"], int(c)) for r in sorted(rows, key=lambda r: r["provider"])
                 for c in r["chunk_ids"]]
        ctx = T.aggregate(text, cands, self.tokens, self.n_global)
        return T.build_prompt(text, self.tokens[ctx], self.width)


def compare(cell, chunks: list, smp: Sample, seed: int, control: bool = False) -> dict:
    """The numbers compared, for the program (or, with ``control``, for the
    control in its place): the cell's reference, on weights its
    architecture's leaf tables remake from the seed."""
    model = cell.model
    ref = Reference(model, chunks)
    err, gap, missing = ref.retrieval(smp.texts, smp.responses, control)
    # teacher forcing reads the prompts the reference lays out from the
    # program's returned ids; the program's own prompts must equal them.
    # The control lays out exactly what the reference does.
    prompts = [ref.prompt(t, r or []) for t, r in zip(smp.texts, smp.responses)]
    diff = 0 if control else sum(
        int(got is None or not np.array_equal(np.asarray(got), want))
        for got, want in zip(smp.prompts, prompts)
    )
    weights = W.make(cell.arch, model, seed, model["torch_dtype"])
    served, ctl = cell.reference.logit_gaps(model, weights, prompts, smp.answers, quantize=fp8 if control else None)
    del weights
    return {
        "logit_gap": float((ctl if control else served).max()) if len(served) else float("inf"),
        "score_err": err,
        "rank_gap": gap,
        "prompt_diff": diff,
        "providers_missing": missing,
        "queries": len(smp.texts),
        "tokens": int(len(served)),
    }


def verdict(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """Each number beside its limit; correct when every one is within."""
    shown = {k: {"value": numbers[k], "limit": limits[k]} for k in limits}
    ok = numbers["queries"] > 0 and all(numbers[k] <= limits[k] for k in limits)
    return ok, shown
