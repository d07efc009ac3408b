"""Plain reference for the federation half of a query: tokenizer, bag
embedder, exact top-m, lexical rerank and prompt layout.

Written from the system's published semantics, independent of the code
under test: the tokenizer hashes each lower-cased word with BLAKE2s into
the vocabulary after 8 special ids; the embedder gives each token id a
Gaussian vector from ``fold_in(PRNGKey(17), id)`` and mean-pools the
non-PAD tokens to a unit vector; a provider returns its top-m chunks by
cosine; the orchestrator reranks all candidates by set overlap with the
query and lays out ``[BOS] CTX chunk SEP ... QRY query ANS``.
"""
from __future__ import annotations

import hashlib

import jax
import jax.numpy as jnp
import numpy as np

PAD, BOS, EOS, SEP, QRY, CTX, ANS = 0, 1, 2, 3, 5, 6, 7
N_SPECIAL = 8
VOCAB = 8192
QUERY_TOKENS = 24  # retrieval reads the first 22 words of a question
QUERY_RESERVE = 32  # prompt tokens kept for the question


def token(word: str) -> int:
    h = hashlib.blake2s(word.lower().encode(), digest_size=4).digest()
    return int.from_bytes(h, "little") % (VOCAB - N_SPECIAL) + N_SPECIAL


def encode(text: str, max_len: int | None = None, bos: bool = True) -> np.ndarray:
    ids = ([BOS] if bos else []) + [token(w) for w in text.split()] + [EOS]
    if max_len is not None:
        ids = ids[:max_len] + [PAD] * max(0, max_len - len(ids))
    return np.asarray(ids, np.int32)


def bag_embed(tokens, dim: int, quantize=None):
    """(N, S) int32 -> (N, dim) f32 unit vectors.  ``quantize`` rounds the
    per-token vectors (the control's lower precision)."""
    key = jax.random.PRNGKey(17)
    vecs = jax.vmap(jax.vmap(lambda t: jax.random.normal(jax.random.fold_in(key, t), (dim,))))(tokens)
    if quantize is not None:
        vecs = quantize(vecs)
    mask = (tokens != PAD).astype(jnp.float32)[..., None]
    pooled = (vecs * mask).sum(1) / jnp.maximum(mask.sum(1), 1.0)
    return pooled / jnp.maximum(jnp.linalg.norm(pooled, axis=-1, keepdims=True), 1e-9)


def embed_rows(rows: np.ndarray, dim: int, batch: int = 256, quantize=None) -> np.ndarray:
    fn = jax.jit(lambda t: bag_embed(t, dim, quantize))
    return np.concatenate([np.asarray(fn(rows[i : i + batch])) for i in range(0, len(rows), batch)])


def overlap_scores(query_tokens: np.ndarray, cand_tokens: np.ndarray) -> np.ndarray:
    q = {int(t) for t in query_tokens if t >= N_SPECIAL}
    out = []
    for row in cand_tokens:
        c = {int(t) for t in row if t >= N_SPECIAL}
        out.append(len(q & c) / (len(q) ** 0.5 * max(len(c), 1) ** 0.5))
    return np.asarray(out, np.float32)


def build_prompt(question: str, context_tokens: np.ndarray, max_len: int) -> np.ndarray:
    query = [int(t) for t in encode(question, bos=False) if t not in (PAD, EOS)]
    reserve = min(QUERY_RESERVE, max(0, (max_len - 4) // 2))
    budget = max_len - 4 - reserve
    ids = [BOS, CTX]
    for row in context_tokens:
        chunk = [int(t) for t in row if t not in (PAD, BOS, EOS)]
        if len(chunk) + 1 > budget:
            break
        ids += chunk + [SEP]
        budget -= len(chunk) + 1
    ids.append(QRY)
    ids += query[: max(0, max_len - len(ids) - 1)]
    ids.append(ANS)
    return np.asarray(ids, np.int32)


def aggregate(question: str, candidates: list[tuple[int, int]], chunk_tokens: np.ndarray,
              n_global: int) -> np.ndarray:
    """Final context of a query: ``candidates`` are ``(provider, chunk_id)``
    in provider order and, within a provider, in its rank order; returns
    the chunk ids of the ``n_global`` best by overlap with the question."""
    ids = np.asarray([c for _, c in candidates])
    scores = overlap_scores(encode(question, max_len=QUERY_TOKENS), chunk_tokens[ids])
    return ids[np.argsort(-scores)[:n_global]]
